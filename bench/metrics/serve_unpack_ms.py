"""serve_unpack_ms: device ms per serve call of the unpack.

The union of the intervals of the server's personalized program's ops
whose scope path runs through ``serve/unpack`` (serve/server.py: the
mixed (B, X) rows re-laid into the model's leaves), in the traced window,
over the calls made in it. Moves ``serve_users_per_s``. Nothing to read
when no such op ran. Also says on standard error how a call's device time
splits between the server's scopes, and how much falls under none (the
layout copies XLA adds carry no scope)."""
from bench import scopes


def read(ctx):
    scopes.log_split(ctx, "serve call", scopes.SERVE_PROGRAM,
                     scopes.serve_part, "calls")
    return scopes.per_run_ms(
        ctx, "serve_unpack_ms", scopes.SERVE_PROGRAM,
        lambda s: scopes.under(s, "serve/unpack"), "serve/unpack", "calls")
