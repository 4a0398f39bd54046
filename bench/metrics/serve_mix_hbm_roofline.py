"""serve_mix_hbm_roofline: the serve mix kernel's share of its HBM
roofline.

Least time: per call, read the S x X int4 plane (X/2 bytes a cluster) and
its float32 block scales and write B x X float32 rows
(``drivers/serve.py``'s ``mix_bytes_per_call``), times the calls made in
the traced window, over the HBM peak. Divided by the device time of the
mix kernel's events in the window. Moves ``serve_users_per_s``. Nothing to
read when no event matches, or when another Pallas kernel runs in the
window."""

# The fused int4 unpack + dequantize + mix (kernels/gossip_mix.py:
# mixture_mix_dequant4), by its instruction name in the trace: its
# pallas_call carries no name=, so the instruction takes the name of the
# server's named scope serve/mix_int4 (serve/server.py), as %mix_int4.1
# does on a v5e. Its one result is the B fp32 rows, at least B x X values.
KERNELS = ("mix_int4",)


def read(ctx):
    calls = ctx.counters.get("calls")
    need = ctx.counters.get("mix_bytes_per_call")
    rows = ctx.counters.get("mix_out_elems")
    if not calls or not need or not rows:
        return None

    def is_mix(o):
        kind, n = o.result
        return o.instruction in KERNELS and kind == "f32" and n >= rows

    w0, w1 = ctx.window
    devs = ctx.trace.devices[:ctx.chips]
    got = [ctx.trace.kernel_ns(d, is_mix, w0, w1) for d in devs]
    others = sorted({n for _, names in got for n in names})
    if others:
        ctx.log(f"serve_mix_hbm_roofline: not read: Pallas kernels other "
                f"than the serve mix ran in the window: {others}")
        return None
    ns = sum(t for t, _ in got) / max(len(devs), 1)
    if ns <= 0:
        return None
    return 100.0 * (calls * need / ctx.peaks["hbm_bytes_per_s"]) / (ns / 1e9)
