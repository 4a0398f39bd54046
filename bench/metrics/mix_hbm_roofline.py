"""mix_hbm_roofline: the exchange kernel's share of its HBM roofline.

Least time: the bytes the exchange needs per round (read the N selected
rows as sent, fp32 or the codec's quanta and scales; write N fp32 rows;
``drivers/train.mix_bytes``) times the rounds in the window, over the HBM
peak. Divided by the device time of the mix kernel's events in the
window. Moves ``round_ms``. Nothing to read when no event matches, or when
another Pallas kernel runs in the window."""

# The exchange's Pallas kernel (kernels/gossip_mix.py: gossip_mix_flat in
# the fp32 cell, gossip_mix_dequant in the int8 cell), by its instruction
# name in the trace. Its pallas_call carries no name= and the Pallas mix
# path no named scope, so the instruction takes the name of the jitted
# round step (core/fedspd.py), as %step_stream_packed.1 does on a v5e. Its
# one result is the N mixed fp32 rows, at least N x X values.
KERNELS = ("step_stream_packed",)


def read(ctx):
    rounds = ctx.counters.get("rounds")
    need = ctx.counters.get("mix_bytes_per_round")
    rows = ctx.counters.get("mix_out_elems")
    if not rounds or not need or not rows:
        return None

    def is_mix(o):
        kind, n = o.result
        return o.instruction in KERNELS and kind == "f32" and n >= rows

    w0, w1 = ctx.window
    devs = ctx.trace.devices[:ctx.chips]
    got = [ctx.trace.kernel_ns(d, is_mix, w0, w1) for d in devs]
    others = sorted({n for _, names in got for n in names})
    if others:
        ctx.log(f"mix_hbm_roofline: not read: Pallas kernels other than "
                f"the exchange's ran in the window: {others}")
        return None
    ns = sum(t for t, _ in got) / max(len(devs), 1)
    if ns <= 0:
        return None
    least = rounds * need / ctx.peaks["hbm_bytes_per_s"]
    return 100.0 * least / (ns / 1e9)
