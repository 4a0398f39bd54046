"""round_local_step_ms: device ms per round of the local step.

The union of the intervals of the round step's ops (its program, as the
trace's ``XLA Modules`` events name it) whose scope path runs through
``fedspd/local_step`` (core/fedspd.py: unpack, the tau SGD steps, pack),
in the traced window, over the rounds completed in it. Moves
``round_ms``. Nothing to read when no such op ran (a program without the
scope)."""
from bench import scopes


def read(ctx):
    return scopes.round_ms(ctx, "round_local_step_ms", "fedspd/local_step")
