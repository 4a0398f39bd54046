"""Training traffic: FedSPD rounds, one after another, through the program's
donated, jitted round step (``launch/steps.make_fedspd_train_step``),
built as ``launch/train`` builds it.

The traffic file (``traffic/<t>.json``, ``"driver": "train"``) gives:
``clients``, ``clusters``, ``tau``, ``batch``, ``seq``, ``token_vocab``,
``docs_per_client``, ``pool`` (distinct batches the window cycles
through), ``graph`` and ``avg_degree``, ``gossip_backend``, ``codec``,
``codec_block``, ``error_feedback`` and ``lr``.

Set-up makes the weights and the batch pool from the seed on the device,
builds the step and drives it through the first ``CHECK_ROUNDS`` rounds,
recording what each round left in the state. The window then carries the
same state on. Afterwards the plain reference replays those first rounds
from the same weights and batches, and the two are compared.
"""
from __future__ import annotations

import gc
import time

import numpy as np

CHECK_ROUNDS = 3


class Rig:
    """The program's round step and everything a run feeds it, built once
    per process for one cell."""

    def __init__(self, cell):
        import jax
        import jax.numpy as jnp

        from bench import weights
        from repro.comm import CommConfig, make_channel
        from repro.core.fedspd import FedSPDConfig
        from repro.core.gossip import GossipSpec, make_mix_fn
        from repro.core.packing import make_pack_spec
        from repro.graphs.topology import make_graph
        from repro.launch import steps

        t = self.t = cell.traffic
        self.cell = cell
        self.run_sizes = cell.sizes["run"]
        self.cfg, self.bundle = cell.model.build(cell.sizes)
        self.n, self.s = t["clients"], t["clusters"]
        self.fcfg = FedSPDConfig(
            n_clients=self.n, n_clusters=self.s, tau=t["tau"],
            batch=t["batch"], lr0=t["lr"], regime="stream")
        self.graph = make_graph(t["graph"], self.n, t["avg_degree"], seed=0)
        self.gossip = GossipSpec.from_graph(self.graph, mode="dense")
        self.comm = CommConfig(codec=t["codec"], block=t["codec_block"],
                               error_feedback=t["error_feedback"])
        shapes = jax.eval_shape(self.bundle.init, jax.random.PRNGKey(0))
        self.spec = make_pack_spec(shapes)
        self.table = weights.leaf_table(shapes)
        if weights.width(self.table) != self.spec.size:
            raise RuntimeError("leaf table and PackSpec disagree on X")
        self.x = self.spec.size
        self.channel = (make_channel(self.comm, self.x)
                        if self.comm.codec != "fp32" else None)
        mix_fn = make_mix_fn(self.gossip, t["gossip_backend"], plane=True,
                             comm=self.comm)
        self.step = steps.make_fedspd_train_step(
            self.bundle, self.gossip, self.fcfg, mix_fn=mix_fn,
            pack_spec=self.spec, donate=True, comm=self.comm)
        rule = getattr(cell.model, "init_rule", weights.default_rule)
        self.make_plane = weights.plane_fn(self.table, (self.s, self.n), rule)
        table = self.table
        self.change_sq = weights.change_sq_fn(table, (self.s, self.n), rule)
        self.tree_change_sq = weights.tree_change_sq_fn(
            table, (self.s, self.n), rule)
        self.sq = jax.jit(lambda p: weights.leaf_sq_norms(p, table))
        audio = self.cfg.family == "audio"
        shape = (t["pool"], self.n, t["batch"], self.cfg.encoder_frames,
                 self.cfg.encoder_d_model or self.cfg.d_model)
        self.make_frames = (jax.jit(lambda k: jax.random.normal(
            k, shape, jnp.float32)) if audio else None)
        self._ref_rounds = {}     # the reference's round, by control or not

    # ---------------------------------------------------------------- data

    def keys(self, seed: int):
        import jax

        from bench import core

        return dict(zip(("weights", "frames", "state"),
                        jax.random.split(core.prng_key(seed), 3)))

    def pool(self, seed: int, keys) -> list:
        """``pool`` distinct batches {tokens (N, B, L), frames?}; the
        documents of one client never repeat inside the pool."""
        import jax.numpy as jnp

        from bench import core
        from bench.tokens import make_mixture_tokens

        t = self.t
        w = core.seed_words(seed, 2)
        docs = make_mixture_tokens(self.n, self.s, t["docs_per_client"],
                                   t["seq"], t["token_vocab"], int(w[0]))
        rng = np.random.default_rng(int(w[1]))
        per = t["pool"] * t["batch"]
        if per > t["docs_per_client"]:
            raise ValueError("pool x batch exceeds docs_per_client")
        idx = np.stack([rng.permutation(t["docs_per_client"])[:per]
                        for _ in range(self.n)])          # (N, P*B)
        tok = docs[np.arange(self.n)[:, None], idx]       # (N, P*B, L)
        tok = tok.reshape(self.n, t["pool"], t["batch"], t["seq"])
        tok = jnp.asarray(np.swapaxes(tok, 0, 1))          # (P, N, B, L)
        frames = (self.make_frames(keys["frames"])
                  if self.make_frames is not None else None)
        out = []
        for p in range(t["pool"]):
            b = {"tokens": tok[p]}
            if frames is not None:
                b["frames"] = frames[p]
            out.append(b)
        return out

    def state(self, keys):
        import jax.numpy as jnp

        from repro.core.fedspd import FedSPDState

        return FedSPDState(
            centers=self.make_plane(keys["weights"]),
            u=jnp.full((self.n, self.s), 1.0 / self.s, jnp.float32),
            z=jnp.zeros((self.n, 1), jnp.int32),
            round=jnp.zeros((), jnp.int32), key=jnp.copy(keys["state"]),
            comm_bytes=jnp.zeros((), jnp.float32),
            ef=(self.channel.init_residual((self.n,))
                if self.channel is not None and self.channel.has_ef
                else None))

    # ------------------------------------------------------ the check rounds

    def check_rounds(self, state, pool, keys, step=None):
        """Drive the step through the first rounds, recording each round's
        per-leaf squared change of the plane since the start, u and the
        selection."""
        step = step or self.step
        recs = []
        for r in range(CHECK_ROUNDS):
            state, m = step(state, pool[r])
            recs.append({"d2": np.asarray(self.change_sq(state.centers,
                                                         keys["weights"])),
                         "u": np.asarray(state.u),
                         "sel": np.asarray(m["selected"])})
        if state.ef is not None:
            recs[-1]["ef2"] = np.asarray(self.sq(state.ef))
        return state, recs

    # ------------------------------------------------------ the reference

    def reference(self, seed: int, pool, follow, state_dtype=None):
        """The plain reference's records of the first rounds, from the same
        weights and batches. ``follow``: the records whose assignments the
        reference takes where its own losses tie (None: its own)."""
        import jax
        import jax.numpy as jnp

        from bench import weights
        from bench.reference import fedspd as ref

        keys = self.keys(seed)
        state_dtype = state_dtype or jnp.float32
        control = state_dtype != jnp.float32
        model, run = self.cell.model, self.run_sizes

        round_fn = self._ref_rounds.get(control)
        if round_fn is None:
            def loss_fn(p, b):
                return model.reference_loss(p, b, run, dtype=(
                    jnp.bfloat16 if control else jnp.float32))

            round_fn = self._ref_rounds[control] = ref.make_round(
                loss_fn, n_clusters=self.s, tau=self.t["tau"],
                lr0=self.t["lr"], lr_decay=self.fcfg.lr_decay,
                u_ema=self.fcfg.u_ema, adj=self.graph.adj,
                tie=self.cell.limits["tie_margin"], state_dtype=state_dtype,
                codec=None if self.channel is None else {
                    "bits": {"int8": 8, "int4": 4}[self.comm.codec],
                    "block": self.comm.block})
        plane = self.make_plane(keys["weights"])
        params = weights.split(plane, self.table)
        del plane
        u = jnp.full((self.n, self.s), 1.0 / self.s, jnp.float32)
        key = jnp.copy(keys["state"])
        ef = (jnp.zeros((self.n, self.x), jnp.float32)
              if self.channel is not None and self.channel.has_ef else None)
        recs, disagree = [], 0
        prec = "default" if control else "highest"
        with jax.default_matmul_precision(prec):
            for r in range(CHECK_ROUNDS):
                zb = None
                if follow is not None and self.t["batch"] == 1:
                    u_prev = (np.full_like(follow[0]["u"], 1.0 / self.s)
                              if r == 0 else follow[r - 1]["u"])
                    zb = ref.assignments_from_u(
                        jnp.asarray(follow[r]["u"]), jnp.asarray(u_prev),
                        self.fcfg.u_ema)
                b = {k: jax.device_put(v, jax.devices()[0])
                     for k, v in pool[r].items()}
                params, u, key, ef, s, dis, unit2 = round_fn(
                    params, u, key, ef, jnp.int32(r), b, zb)
                recs.append({"d2": np.asarray(self.tree_change_sq(
                                 params, keys["weights"])),
                             "u": np.asarray(u), "sel": np.asarray(s),
                             "unit2": np.asarray(unit2)})
                disagree += int(dis)
        if ef is not None:
            recs[-1]["ef2"] = np.asarray(self.sq(ef))
        return recs, disagree


def compare(prog, ref, disagree: int, limits: dict) -> list:
    """The numbers the check compares, each beside its limit."""
    from bench.core import Check

    # per-leaf scale: the largest single step lr * g the reference's whole
    # batch would take on that leaf, or the median leaf's, whichever is
    # larger. Mixing moves a row by the clients' difference, the same on
    # both sides; the gap is read in units of a gradient step.
    unit = np.sqrt(np.max(np.stack([r["unit2"] for r in ref]), axis=0))
    moved = unit[unit > 0]
    den = np.maximum(unit, float(np.median(moved)) if moved.size else 1.0)

    def change_gap(dp2, dr2):
        return float(np.max(np.abs(np.sqrt(dp2) - np.sqrt(dr2)) / den))

    sel = sum(int(np.sum(p["sel"] != r["sel"])) for p, r in zip(prog, ref))
    u_gap = max(float(np.max(np.abs(p["u"] - r["u"])))
                for p, r in zip(prog, ref))
    vals = {
        "selection_mismatch": float(sel),
        "assignment_mismatch": float(disagree),
        "u_gap": u_gap,
        "change1_gap": change_gap(prog[0]["d2"], ref[0]["d2"]),
        "change3_gap": change_gap(prog[-1]["d2"], ref[-1]["d2"]),
    }
    if "ef2" in ref[-1]:
        # the error-feedback residual each client keeps after the rounds,
        # by (client, leaf), against the reference's or the median's norm
        ep, er = np.sqrt(prog[-1]["ef2"]), np.sqrt(ref[-1]["ef2"])
        vals["ef_gap"] = float(np.max(np.abs(ep - er) / np.maximum(
            er, float(np.median(er)))))
    return [Check(k, v, float(limits[k])) for k, v in vals.items()]


def run(ctx):
    from bench import core

    cell = ctx.cell
    rig = Rig(cell)
    keys = rig.keys(ctx.seed)
    pool = rig.pool(ctx.seed, keys)
    state = rig.state(keys)
    step = ctx.test_hooks.get("step", lambda s: s)(rig.step)
    ctx.log(f"{cell.name}: X={rig.x:,} N={rig.n} S={rig.s}; check rounds")
    state, recs = rig.check_rounds(state, pool, keys, step)
    ctx.log("window")

    rounds, bad = 0, 0
    with ctx.traced():
        with core.span("bench/window"):
            c0 = ctx.compiles.count
            t0 = time.perf_counter()
            setup_s = t0 - ctx.t_start
            while True:
                with core.span("bench/dispatch"):
                    state, m = step(state, pool[(CHECK_ROUNDS + rounds)
                                                % len(pool)])
                with core.span("bench/wait"):
                    cons = np.asarray(m["consensus"])
                rounds += 1
                bad += int(not np.all(np.isfinite(cons)))
                if time.perf_counter() - t0 >= ctx.seconds:
                    break
            t1 = time.perf_counter()
    in_window = ctx.compiles.count - c0
    mem = core.memory_peak_bytes(ctx.devices)
    round_s = (t1 - t0) / rounds
    ctx.log(f"{rounds} rounds in {t1 - t0:.3f}s ({round_s * 1e3:.3f} ms a "
            f"round); set-up {setup_s:.2f}s; compiles in window "
            f"{in_window}; memory peak {mem:,} B")
    del state, m, step
    rig.step = None
    gc.collect()

    t_ref = time.perf_counter()
    ref_recs, disagree = rig.reference(ctx.seed, pool, follow=recs)
    checks = compare(recs, ref_recs, disagree, cell.limits["limits"])
    ctx.log(f"reference: {time.perf_counter() - t_ref:.2f}s")

    fwd = cell.model.flops_per_sample(rig.run_sizes, rig.t["seq"])
    t = rig.t
    per_client = t["batch"] * fwd * (rig.s + 3 * t["tau"])
    counters = {
        "rounds": rounds,
        "flops_per_round": rig.n * per_client,
        "mix_bytes_per_round": mix_bytes(rig),
        "mix_out_elems": rig.n * rig.x,
    }
    return core.Outcome(
        attempted=rounds, failed=bad,
        end_to_end={"round_ms": round_s * 1e3, "setup_s": setup_s},
        counters=counters, checks=checks, memory_peak_bytes=mem)


def mix_bytes(rig) -> float:
    """HBM bytes the exchange needs per round, whatever implements it: read
    the N selected rows as sent (fp32, or the codec's quanta and scales)
    and write N fp32 rows."""
    n, x = rig.n, rig.x
    if rig.channel is None:
        sent = n * x * 4
    else:
        nq = -(-x // rig.comm.block)
        sent = n * (nq * rig.comm.block * 1 + nq * 4)
    return float(sent + n * x * 4)


def readings(cell, seeds, faults=()):
    """Yield (seed, kind, {number: value}) for the program, each planted
    fault and the control, over the first rounds only (``control.py``)."""
    import jax.numpy as jnp

    from bench import faults as fault_mod

    rig = Rig(cell)
    limits = cell.limits["limits"]
    for seed in seeds:
        keys = rig.keys(seed)
        pool = rig.pool(seed, keys)
        kinds = [("program", rig.step)] + [
            (f, fault_mod.train_step(f, rig)) for f in faults]
        for kind, step in kinds:
            state, recs = rig.check_rounds(rig.state(keys), pool, keys,
                                            step)
            del state
            gc.collect()
            ref, dis = rig.reference(seed, pool, follow=recs)
            yield seed, kind, {c.name: c.value for c in
                               compare(recs, ref, dis, limits)}
        ctl, _ = rig.reference(seed, pool, follow=None,
                               state_dtype=jnp.bfloat16)
        ref, dis = rig.reference(seed, pool, follow=ctl)
        yield seed, "control", {c.name: c.value for c in
                                compare(ctl, ref, dis, limits)}
