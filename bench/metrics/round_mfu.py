"""round_mfu: the whole round's share of the chips' bf16 peak.

Model FLOPs of the rounds completed in the traced window (the
configuration's forward count per example times (S + 3 tau) per client,
times N; recomputation not counted) over the window's length times the
chips times the bf16 peak of ``peaks.json``. Moves ``round_ms``."""


def read(ctx):
    rounds = ctx.counters.get("rounds")
    flops = ctx.counters.get("flops_per_round")
    if not rounds or not flops:
        return None
    w0, w1 = ctx.window
    seconds = (w1 - w0) / 1e9
    return 100.0 * rounds * flops / (
        seconds * ctx.chips * ctx.peaks["bf16_flops_per_s"])
