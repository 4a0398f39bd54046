"""serve_dispatch_ms: median host ms of the server's dispatch.

The median length of the ``serve/dispatch`` host spans that start in the
traced window (serve/server.py: the jitted entry point called until it
returns, before the wait for the device in ``serve/block``). Moves
``serve_users_per_s``. Nothing to read when the server writes no such
span. Also says on standard error where the device idled: the longest
gaps, each named by the harness span and the server's innermost span
that covered it."""
from bench import scopes


def read(ctx):
    value = scopes.median_span_ms(ctx, "serve_dispatch_ms", "serve/dispatch")
    sc = scopes.of(ctx)
    if sc is not None:
        for d in sc.devices[:ctx.chips]:
            gaps = ", ".join(f"{n} {s * 1e3:.3f}"
                             for n, s in sc.idle_gaps(d, *ctx.window))
            ctx.log(f"idle gaps on {d} (ms): {gaps}")
    return value
