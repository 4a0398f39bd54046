"""Serving traffic: open-loop requests for personalized models (Eq. (2))
off one resident quantized cluster plane, through the program's
``ClusterPlaneServer.personalized`` on a plane loaded the way
``from_artifact`` loads one.

The traffic file (``traffic/<t>.json``, ``"driver": "serve"``) gives:
``codec`` and ``qblock`` of the plane, ``clusters``, ``users`` (the table
requests are drawn from), ``zipf`` (the exponent of user popularity),
``dirichlet`` (each user's mixture over the clusters), ``max_batch`` (the
harness sends up to that many waiting requests per call, padded to it:
one compiled shape), ``rate_per_s`` (the open-loop arrival rate, fixed
per cell) and ``check_share`` (the share of requests whose answers the
check compares).

Arrivals: ``rate_per_s * seconds`` requests whose gaps are the
exponential quantiles in one fixed shuffled order, the same for every
seed: a queue's tail depends on the order of its gaps. The seed draws
the users, their mixtures and the checked requests. Requests are served
first come, first served. When the window closes no more are sent; the
call in flight is waited for, and the rate counts the requests answered
inside the window. Offered above capacity, the queue never empties, so
the rate reads the server's capacity, up to the offered rate.
"""
from __future__ import annotations

import contextlib
import gc
import math
import time

import numpy as np

ARRIVAL_ORDER = 20240917   # the one fixed shuffle of the arrival gaps


@contextlib.contextmanager
def _no_persistent_cache():
    """The server embeds its plane, made from the seed, in the program it
    compiles, so no later run can load that program from the cache: do
    not write it there."""
    import jax
    from jax.experimental.compilation_cache import compilation_cache as cc

    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    try:
        yield
    finally:
        jax.config.update("jax_enable_compilation_cache", True)
        cc.reset_cache()


def fingerprint_fn(table):
    """jitted: personalized pytree (B leaves in flatten order) ->
    (B, L, 2) float32: per row and leaf, the sum of the values times a
    fixed +-1 pattern of the flat index, and the sum of squares. Any
    change to a row moves one or the other."""
    import jax
    import jax.numpy as jnp

    def signs(leaf):
        idx = (jnp.arange(leaf.size, dtype=jnp.uint32)
               + jnp.uint32(leaf.offset))
        h = idx * jnp.uint32(2654435761)
        h = h ^ (h >> 16)
        return 1.0 - 2.0 * ((h >> 7) & 1).astype(jnp.float32)

    def fp(leaves):
        out = []
        for x, leaf in zip(leaves, table):
            x = x.reshape(x.shape[0], -1).astype(jnp.float32)
            out.append(jnp.stack([jnp.sum(x * signs(leaf), -1),
                                  jnp.sum(x * x, -1)], -1))
        return jnp.stack(out, 1)

    return jax.jit(lambda tree: fp(jax.tree.leaves(tree)))


class Rig:
    def __init__(self, cell):
        import jax
        import jax.numpy as jnp

        from bench import weights
        from repro.core.packing import make_pack_spec

        t = self.t = cell.traffic
        self.cell = cell
        self.cfg, self.bundle = cell.model.build(cell.sizes)
        shapes = jax.eval_shape(self.bundle.init, jax.random.PRNGKey(0))
        self.spec = make_pack_spec(shapes)
        self.table = weights.leaf_table(shapes)
        self.x = self.spec.size
        qb, s = t["qblock"], t["clusters"]
        self.xp = -(-self.x // qb) * qb
        x, xp = self.x, self.xp

        def codes(key):
            kq, ks = jax.random.split(key)
            q = jax.random.randint(kq, (s, xp), -8, 8, jnp.int8)
            q = jnp.where(jnp.arange(xp) < x, q, 0)   # the padded tail is 0
            sc = jax.random.uniform(ks, (s, xp // qb), jnp.float32,
                                    1e-3, 1e-2)
            return q, sc.astype(jnp.float16).astype(jnp.float32)

        self.make_codes = jax.jit(codes)
        self.fingerprint = fingerprint_fn(self.table)

    def server(self, q, sc):
        """The server, from an artifact assembled as ``load_servable``
        assembles one."""
        from repro.checkpoint.ckpt import CkptManifest
        from repro.comm.codecs import int4_pack
        from repro.serve import ClusterPlaneServer
        from repro.serve.artifact import ServableArtifact

        t = self.t
        q, sc = np.asarray(q), np.asarray(sc)
        man = CkptManifest(kind="servable", arch=self.cfg.name,
                           n_clusters=t["clusters"],
                           plane_shape=(t["clusters"], self.x),
                           pack_digest=self.spec.digest, codec=t["codec"],
                           qblock=t["qblock"])
        art = ServableArtifact(manifest=man, plane_q=q, plane_scale=sc)
        if t["codec"] == "int4":
            art.plane_packed = np.asarray(int4_pack(q))
        return ClusterPlaneServer.from_artifact(art, self.spec)

    def schedule(self, seed: int, seconds: float):
        """(due seconds, mixtures (n, S)) of the requests in the window."""
        from bench import core

        t = self.t
        n = max(1, int(round(t["rate_per_s"] * seconds)))
        w = core.seed_words(seed, 2)
        rng = np.random.default_rng(int(w[0]))
        gaps = -np.log1p(-(np.arange(n) + 0.5) / n) / t["rate_per_s"]
        gaps = np.random.default_rng(ARRIVAL_ORDER).permutation(gaps)
        due = np.cumsum(gaps) - gaps[0]
        due *= seconds * (n - 0.5) / n / max(due[-1], 1e-12)
        ranks = np.arange(1, t["users"] + 1, dtype=np.float64)
        cdf = np.cumsum(ranks ** -t["zipf"])
        users = np.searchsorted(cdf, rng.uniform(0, cdf[-1], n))
        # each user's own mixture: one draw per distinct user, in user order
        uniq, inv = np.unique(users, return_inverse=True)
        mix = np.random.default_rng(int(w[1])).dirichlet(
            np.full(t["clusters"], t["dirichlet"]), size=uniq.size)[inv]
        return due, mix.astype(np.float32)

    def reference_fingerprints(self, q, sc, mix, control: bool = False):
        """The plain reference's fingerprints of the answers with these
        mixtures: dequantize every code times its block's scale, mix in
        float32 at full matmul precision (the control: bfloat16)."""
        import jax
        import jax.numpy as jnp

        from bench import weights

        qb, x = self.t["qblock"], self.x
        dt = jnp.bfloat16 if control else jnp.float32

        def answers(q, sc, u):
            deq = (q.astype(dt) * jnp.repeat(sc.astype(dt), qb, axis=1)
                   )[:, :x]
            out = jnp.einsum("bs,sx->bx", u.astype(dt), deq,
                             precision=jax.lax.Precision.HIGHEST)
            return weights.split(out.astype(jnp.float32), self.table)

        fn = jax.jit(lambda q, sc, u: self.fingerprint(answers(q, sc, u)))
        b = self.t["max_batch"]
        out = []
        for i in range(0, len(mix), b):
            u = np.zeros((b, mix.shape[1]), np.float32)
            part = mix[i:i + b]
            u[:len(part)] = part
            out.append(np.asarray(fn(q, sc, jnp.asarray(u)))[:len(part)])
        return np.concatenate(out)


def call_bytes(x: int, s: int, b: int, qblock: int) -> float:
    """HBM bytes one call needs: read the S x X int4 plane (half a byte a
    value) and its float32 block scales, write B x X float32 rows."""
    return float(s * x / 2 + s * (x / qblock) * 4 + b * x * 4)


def call_flops(x: int, s: int, b: int) -> float:
    """Operations of one call: B rows mixing S dequantized clusters
    (2 B S X) after dequantizing each value once (S X)."""
    return float(2 * b * s * x + s * x)


def answer_gap(fp_prog, fp_ref) -> float:
    """Worst (request, leaf) gap: the +-1 sums' difference and the norms'
    difference, each over the reference's norm of that leaf."""
    norm = np.sqrt(np.maximum(fp_ref[..., 1], 1e-30))
    dot = np.abs(fp_prog[..., 0] - fp_ref[..., 0]) / norm
    nrm = np.abs(np.sqrt(np.maximum(fp_prog[..., 1], 0)) - norm) / norm
    return float(max(dot.max(), nrm.max()))


def compare_answers(rig, q, sc, mix, fps) -> float:
    """``answer_gap`` of the checked answers (fingerprints keyed by
    request) against the reference's; inf where none was checked."""
    keys = sorted(fps)
    if not keys:
        return math.inf
    ref = rig.reference_fingerprints(q, sc, mix[keys])
    return answer_gap(np.stack([fps[k] for k in keys]), ref)


def checked_requests(seed: int, n: int, share: float):
    """(n,) bool: the requests whose answers the check compares, each drawn
    from the seed with probability ``share``."""
    from bench import core

    rng = np.random.default_rng(int(core.seed_words(seed, 3)[2]))
    return rng.random(n) < share


def serve_loop(call, due, mix, seconds, max_batch, checked, fingerprint,
               span):
    """First come, first served: send up to ``max_batch`` of the requests
    that are due per call, padded, until the window closes; then wait for
    the call in flight. Returns each request's completion time from the
    window's start (nan where not sent), the number of calls made (all of
    them inside the traced span), and the fingerprints of the checked
    requests' answers keyed by request."""
    import jax

    n, s = mix.shape
    done = np.full(n, np.nan)
    fps, calls, head = {}, 0, 0
    t0 = time.perf_counter()
    while head < n:
        now = time.perf_counter() - t0
        if now >= seconds:
            break
        due_n = int(np.searchsorted(due, now, side="right"))
        if due_n <= head:
            wait = due[head] - now
            if wait > 0.002:
                time.sleep(wait - 0.001)
            continue
        take = np.arange(head, min(head + max_batch, due_n))
        head = int(take[-1]) + 1
        u = np.zeros((max_batch, s), np.float32)
        u[:take.size] = mix[take]
        with span("bench/dispatch"):
            out = call(u)
        with span("bench/wait"):
            jax.block_until_ready(out)
        done[take] = time.perf_counter() - t0
        calls += 1
        hits = np.flatnonzero(checked[take])
        if hits.size:
            with span("bench/check"):
                fp = np.asarray(fingerprint(out))
            for j in hits:
                fps[int(take[j])] = fp[j]
        del out
    return done, calls, fps


def run(ctx):
    import jax

    from bench import core

    cell, t = ctx.cell, ctx.cell.traffic
    rig = Rig(cell)
    key = core.prng_key(ctx.seed)
    q, sc = rig.make_codes(key)
    server = rig.server(q, sc)
    call = ctx.test_hooks.get("call", lambda s: s.personalized)(server)
    due, mix = rig.schedule(ctx.seed, ctx.seconds)
    n = len(due)
    checked = checked_requests(ctx.seed, n, t["check_share"])
    ctx.log(f"{cell.name}: X={rig.x:,} S={t['clusters']} {n} requests at "
            f"{t['rate_per_s']}/s; warm-up")
    with _no_persistent_cache():
        warm = call(np.zeros((t["max_batch"], t["clusters"]), np.float32))
        jax.block_until_ready(warm)
    jax.block_until_ready(rig.fingerprint(warm))
    del warm
    ctx.log("window")

    with ctx.traced():
        with core.span("bench/window"):
            c0 = ctx.compiles.count
            setup_s = time.perf_counter() - ctx.t_start
            done, calls, fps = serve_loop(
                call, due, mix, ctx.seconds, t["max_batch"], checked,
                rig.fingerprint, core.span)
    in_window = ctx.compiles.count - c0
    mem = core.memory_peak_bytes(ctx.devices)
    sent = np.isfinite(done)      # every request sent is waited for
    in_time = int(np.sum(done <= ctx.seconds))
    ctx.log(f"{int(sent.sum())} of {n} offered sent, {in_time} answered in "
            f"the window; {calls} calls; set-up {setup_s:.2f}s; compiles "
            f"in window {in_window}; memory peak {mem:,} B")
    ends = np.diff(np.unique(done[sent]))
    if ends.size:
        ctx.log(f"between call ends: median {np.median(ends) * 1e3:.2f} ms, "
                f"longest {ends.max() * 1e3:.2f} ms, "
                f"{int(np.sum(ends > 0.06))} over 60 ms")
    del server, call
    gc.collect()

    from bench.core import Check

    checks = [Check("answer_gap", compare_answers(rig, q, sc, mix, fps),
                    float(cell.limits["limits"]["answer_gap"]))]
    ctx.log(f"checked {len(fps)} answers")
    counters = {
        "calls": calls,
        "mix_bytes_per_call": call_bytes(rig.x, t["clusters"],
                                         t["max_batch"], t["qblock"]),
        "mix_out_elems": t["max_batch"] * rig.x,
        "flops_per_call": call_flops(rig.x, t["clusters"], t["max_batch"]),
    }
    return core.Outcome(
        attempted=int(sent.sum()), failed=0,
        end_to_end={"serve_users_per_s": in_time / ctx.seconds,
                    "setup_s": setup_s},
        counters=counters, checks=checks, memory_peak_bytes=mem)


def readings(cell, seeds, faults=(), seconds: float = 2.0):
    """Yield (seed, kind, {number: value}) for the program, each planted
    fault and the control: a short window at the cell's own load, its
    answers checked as a run checks them (``control.py``)."""
    import contextlib as cl

    from bench import core
    from bench import faults as fault_mod

    t = cell.traffic
    rig = Rig(cell)
    with _no_persistent_cache():
        for seed in seeds:
            q, sc = rig.make_codes(core.prng_key(seed))
            server = rig.server(q, sc)
            due, mix = rig.schedule(seed, seconds)
            checked = checked_requests(seed, len(due), t["check_share"])
            kinds = [("program", server.personalized)] + [
                (f, fault_mod.serve_call(f, server)) for f in faults]
            for kind, call in kinds:
                _, _, fps = serve_loop(
                    call, due, mix, seconds, t["max_batch"], checked,
                    rig.fingerprint, lambda _n: cl.nullcontext())
                if kind == "program":
                    keys = sorted(fps)
                yield seed, kind, {"answer_gap": compare_answers(
                    rig, q, sc, mix, fps)}
            ctl = dict(zip(keys, rig.reference_fingerprints(
                q, sc, mix[keys], control=True)))
            yield seed, "control", {"answer_gap": compare_answers(
                rig, q, sc, mix, ctl)}
            del server
            gc.collect()
