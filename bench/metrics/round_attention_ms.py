"""round_attention_ms: device ms per round of attention.

The union of the intervals of the round step's ops whose scope path runs
through an ``attn`` segment (models/attention.py: reference and blocked
attention, forward and backward, under ``fedspd/local_step`` or
``fedspd/cluster``), in the traced window, over the rounds completed in
it. Moves ``round_ms``. Nothing to read when no such op ran."""
from bench import scopes


def read(ctx):
    return scopes.round_ms(ctx, "round_attention_ms", "attn")
