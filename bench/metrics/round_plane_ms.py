"""round_plane_ms: device ms per round of the passes over the (S, N, X)
parameter plane outside the exchange kernel.

The union of the intervals of the round step's ops whose scope path runs
through ``fedspd/select`` (the cluster draw and the S-way row select) or
``fedspd/scatter`` (the mixed rows written back into the plane), in the
traced window, over the rounds completed in it. Moves ``round_ms``.
Nothing to read when no such op ran."""
from bench import scopes


def read(ctx):
    return scopes.round_ms(ctx, "round_plane_ms", "fedspd/select",
                           "fedspd/scatter")
