"""serve_mfu: the whole serve step's share of the chips' bf16 peak.

Operations of the calls completed in the traced window (per call:
2 B S X for the mixture plus S X for the dequantization) over the
window's length times the chips times the bf16 peak of ``peaks.json``.
The step moves bytes, not operations, so this share is small; it bounds
what a change that takes the mix kernel off the path can claim. Moves
``serve_users_per_s``."""


def read(ctx):
    calls = ctx.counters.get("calls")
    flops = ctx.counters.get("flops_per_call")
    if not calls or not flops:
        return None
    w0, w1 = ctx.window
    return 100.0 * calls * flops / (
        (w1 - w0) / 1e9 * ctx.chips * ctx.peaks["bf16_flops_per_s"])
