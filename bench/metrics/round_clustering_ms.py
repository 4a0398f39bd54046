"""round_clustering_ms: device ms per round of clustering.

The union of the intervals of the round step's ops whose scope path runs
through ``fedspd/cluster`` (core/fedspd.py: the per-batch assignment
forward and the ``u`` update in the stream regime, the re-clustering in
the full one), in the traced window, over the rounds completed in it.
Moves ``round_ms``. Nothing to read when no such op ran."""
from bench import scopes


def read(ctx):
    return scopes.round_ms(ctx, "round_clustering_ms", "fedspd/cluster")
