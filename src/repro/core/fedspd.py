"""FedSPD: Soft-clustering Personalized Decentralized FL (paper Algorithm 1).

Round structure (Section 4):
  1. LocalUpdate       — each client i samples s_i ~ Categorical(u_i) and
                         runs τ SGD steps on c_{i,s_i} using data currently
                         assigned to cluster s_i;
  2. ParameterExchange — broadcast (s_i, c_{i,s_i}) to graph neighbors;
  3. ParameterUpdate   — closed-neighborhood average over matching
                         selections (Eq. (1); core/gossip.py);
  4. DataClustering    — relabel every local point by min-loss center and
                         recompute u (core/clustering.py).
FinalPhase (Eq. (2)): x_i = Σ_s u_{i,s} c_{i,s}, then τ_final local epochs
on all of D_i.

Everything is a single jitted step vmapped over the client axis, so the same
code runs the paper-scale CPU experiments and the mesh-sharded production
configs (launch/ shards the client axis and model dims).

Two data regimes:
- ``full``   (paper-faithful): persistent per-point assignments z over each
  client's entire local dataset; clustering re-evaluates all M points.
- ``stream`` (production): each round consumes a fresh batch; assignments
  are computed per-batch, training uses a cluster-masked loss, and u is
  updated as an EMA of batch assignment fractions. Used by launch/train.

Two parameter representations (``make_round_step(pack_spec=...)``):
- pytree (reference): ``state.centers`` has leaves (S, N, ...); every
  cross-client stage walks the tree leaf-by-leaf.
- packed plane (core/packing.py): ``state.centers`` is ONE (S, N, X)
  fp32 buffer; gather/scatter are single-array indexing, DP clip+noise is
  one flat L2 norm + fused scale-and-noise over (N, X), gossip mixes the
  whole plane in one pass (exactly one ``pallas_call`` on the Pallas
  backend), consensus and Eq. (2) are flat reductions. Models re-enter
  pytree form only where gradients/forwards need model structure (the
  local-SGD inner loop and the clustering forward) and at the API
  boundary (init, eval, checkpoint). Parity with the pytree path is
  asserted in tests/test_packing.py.

The round step is pure in (state, train, key, lr) with static shapes, so
the experiment drivers can either dispatch it once per round (the Python
loop engine) or trace it as the body of a whole-experiment ``lax.scan``
(``RunConfig(scan_rounds=True)``: all R rounds in one compiled program,
adjacency schedule as scan xs, metric curve as scan ys) — both engines
produce bit-identical states because the step draws nothing from host
state (tests/test_scan_rounds.py).
"""
from __future__ import annotations

import dataclasses
from typing import Any, Callable, NamedTuple, Optional

import jax
import jax.numpy as jnp

from repro.core.clustering import cluster_all_clients, mixture_coefficients
from repro.core.gossip import (
    GossipSpec,
    consensus_distance,
    fedspd_weight_matrix,
    mix,
    round_comm_bytes,
)
from repro.core.packing import PackSpec, pack, unpack
from repro.core.sparse import column_activity, maybe_update_mask
from repro.data.pipeline import client_batches, client_uniform_batches
from repro.optim.sgd import Optimizer, sgd
from repro.utils.pytree import (
    tree_bytes,
    tree_weighted_sum,
)

PyTree = Any


class FedSPDState(NamedTuple):
    centers: PyTree      # leaves (S, N, ...): client i's estimate of center s
    u: jnp.ndarray       # (N, S) mixture coefficients
    z: jnp.ndarray       # (N, M) per-point assignments ("full" regime)
    round: jnp.ndarray   # () int32
    key: jax.Array
    comm_bytes: jnp.ndarray  # () float32 cumulative LOGICAL bytes
    ef: Any = None       # (N, X) error-feedback residual (comm/codecs);
    #                      None (an empty pytree subtree) unless the run
    #                      uses a compressing codec with error_feedback
    mask: Any = None     # (N, X) float {0,1} per-client sparse masks
    #                      (core/sparse; DisPFL) — None unless the run
    #                      carries a SparseConfig


@dataclasses.dataclass(frozen=True)
class FedSPDConfig:
    n_clients: int
    n_clusters: int
    tau: int = 5                  # local steps per round
    batch: int = 32
    lr0: float = 5e-2
    lr_decay: float = 0.98        # per-round multiplicative decay
    tau_final: int = 10
    final_lr_scale: float = 0.5
    cluster_chunk: Optional[int] = None
    u_ema: float = 0.3            # "stream" regime u update rate
    regime: str = "full"          # full | stream
    point_to_point: bool = True   # comm accounting mode

    # --- differential privacy (paper B.2.6, following Wei et al. 2020) ---
    # each round's local update delta is L2-clipped to dp_clip and Gaussian
    # noise with std dp_clip * dp_noise_multiplier is added BEFORE the
    # parameter exchange; 0 disables. noise multiplier c = sqrt(2 ln(1.25/δ))/ε.
    dp_clip: float = 0.0
    dp_noise_multiplier: float = 0.0


def init_state(
    key: jax.Array,
    model_init: Callable[[jax.Array], PyTree],
    cfg: FedSPDConfig,
    data_m: int,
) -> FedSPDState:
    """Independent random init per (cluster, client) pair — consensus within
    each cluster emerges from gossip, exactly the DFL setting."""
    k_init, k_state = jax.random.split(key)
    keys = jax.random.split(k_init, cfg.n_clusters * cfg.n_clients)
    keys = keys.reshape(cfg.n_clusters, cfg.n_clients, -1)
    centers = jax.vmap(jax.vmap(model_init))(keys)
    # explicit dtype: a weak-typed u would retrigger jit on the second round
    u = jnp.full((cfg.n_clients, cfg.n_clusters), 1.0 / cfg.n_clusters,
                 jnp.float32)
    z = jnp.zeros((cfg.n_clients, data_m), jnp.int32)
    return FedSPDState(
        centers=centers, u=u, z=z, round=jnp.zeros((), jnp.int32),
        key=k_state, comm_bytes=jnp.zeros((), jnp.float32),
    )


def seeded_init(
    key: jax.Array,
    model_init: Callable[[jax.Array], PyTree],
    cfg: FedSPDConfig,
    loss_fn: Callable,
    data: dict,  # leaves (N, M, ...) — "full" regime layout
    *,
    epochs: int = 15,
    lr: float = 0.1,
    optimizer: Optimizer = None,
) -> FedSPDState:
    """Client-seeded warm start (k-means++-flavoured, no ground truth).

    S distinct randomly-chosen clients each pretrain one cluster center on
    their OWN raw local data, then flood-broadcast it (comm cost: S models,
    once). Because client mixtures differ (U[0.1, 0.9] in the paper's
    construction), the S seeds start genuinely separated — which is what
    Assumption 5.6 (bounded distance to the optimal centers at every step)
    asks of the initialization. Random symmetric inits frequently collapse
    both centers onto one compromise model (EM local optimum); see
    EXPERIMENTS.md §Accuracy for the ablation.
    """
    optimizer = optimizer or sgd()
    state = init_state(key, model_init, cfg, jax.tree.leaves(data)[0].shape[1])
    k_pick, k_run = jax.random.split(jax.random.fold_in(key, 1))
    n = cfg.n_clients
    seeds = jax.random.choice(k_pick, n, (cfg.n_clusters,), replace=False)
    m = jax.tree.leaves(data)[0].shape[1]
    steps = epochs * max(1, m // cfg.batch)
    grad_fn = jax.grad(loss_fn)

    def pretrain_one(s_idx, seed_client):
        # distinct subkeys: reusing fold_in(k_run, s_idx) for BOTH the model
        # init and the batch-sampling scan would correlate the init weights
        # with the batch sequence (same underlying key stream)
        k_model, k_scan = jax.random.split(jax.random.fold_in(k_run, s_idx))
        p = model_init(k_model)
        x_i = jax.tree.map(lambda l: l[seed_client], data)
        batch_all = {"x": x_i["inputs"], "y": x_i["targets"]}
        opt_s = optimizer.init(p)

        def one(carry, k):
            p, opt_s = carry
            idx = jax.random.randint(k, (cfg.batch,), 0, m)
            b = {"x": batch_all["x"][idx], "y": batch_all["y"][idx]}
            p, opt_s = optimizer.update(grad_fn(p, b), opt_s, p, lr)
            return (p, opt_s), None

        (p, _), _ = jax.lax.scan(
            one, (p, opt_s), jax.random.split(k_scan, steps)
        )
        return p

    centers = [pretrain_one(s, seeds[s]) for s in range(cfg.n_clusters)]
    stacked = jax.tree.map(
        lambda *ls: jnp.stack([jnp.broadcast_to(l, (n,) + l.shape) for l in ls]),
        *centers,
    )
    return state._replace(centers=stacked)


def select_clusters(key: jax.Array, u: jnp.ndarray) -> jnp.ndarray:
    """Step 1a: s_i ~ Categorical(u_i)."""
    return jax.random.categorical(key, jnp.log(jnp.maximum(u, 1e-12)), axis=-1)


def _gather_selected(centers: PyTree, s: jnp.ndarray) -> PyTree:
    """centers leaves (S, N, ...) -> selected (N, ...)."""
    n = s.shape[0]
    return jax.tree.map(lambda l: l[s, jnp.arange(n)], centers)


def _select_rows(plane: jnp.ndarray, s: jnp.ndarray) -> jnp.ndarray:
    """(S, N, X) plane -> (N, X): client i's row of cluster s_i, as an
    S-way select. The equivalent row gather ``plane[s, arange(N)]``
    lowers on TPU to one mini-gather per 32k columns — thousands of ops at
    a full model's X, which dominated the round step's compile time."""
    rows = plane[0]
    for k in range(1, plane.shape[0]):
        rows = jnp.where((s == k)[:, None], plane[k], rows)
    return rows


def _write_rows(plane: jnp.ndarray, s: jnp.ndarray,
                rows: jnp.ndarray) -> jnp.ndarray:
    """The inverse of ``_select_rows``: client i's row goes into cluster
    s_i of the (S, N, X) plane, the other rows stay. One broadcast select
    whose output sits at the input's index, so XLA updates a donated plane
    in place; the equivalent ``plane.at[s, arange(N)].set(rows)`` lowers
    on TPU to a loop of dynamic-update-slices at about 5% of a v5e's HBM
    bandwidth. The barrier keeps XLA from fusing the select into the
    plane's readers (the consensus reductions), which would then each
    read the old plane and the rows in place of the written plane."""
    hit = jnp.arange(plane.shape[0])[:, None] == s[None, :]      # (S, N)
    return jax.lax.optimization_barrier(
        jnp.where(hit[:, :, None], rows[None].astype(plane.dtype), plane))


def _scatter_selected(centers: PyTree, s: jnp.ndarray, value: PyTree) -> PyTree:
    n = s.shape[0]
    return jax.tree.map(
        lambda l, v: l.at[s, jnp.arange(n)].set(v.astype(l.dtype)),
        centers, value,
    )


def make_round_step(
    loss_fn: Callable,              # (params, batch) -> scalar
    per_example_loss: Callable,     # (params, batch) -> (B,)
    gossip: GossipSpec,
    cfg: FedSPDConfig,
    optimizer: Optimizer = None,
    lr_schedule: Callable = None,
    mix_fn: Callable = None,        # (c_sel, s) -> mixed; default Eq. (1)
    pack_spec: Optional[PackSpec] = None,  # packed (S, N, X) engine
    model_bytes: Optional[int] = None,     # per-model wire bytes (hoisted)
    donate: bool = False,           # jit + donate the state in place
    comm=None,                      # comm/codecs.CommConfig: wire codec
    sparse=None,                    # core/sparse.SparseConfig: DisPFL masks
):
    """Returns step(state, data, adj=None) -> (state, metrics). ``data``
    leaves: (N, M, ...) in the "full" regime; (N, B, ...) fresh batch in
    "stream".

    ``adj`` is the scenario engine's dynamic-topology hook: THIS ROUND's
    (N, N) adjacency as a TRACED input (time-varying rewire schedules,
    Bernoulli link dropout, per-seed graphs under vmap) instead of the
    gossip spec's closure constant. The weight matrix is rebuilt from it
    each round with row renormalization over the surviving links, and the
    comm accounting charges only active links — a dropped edge costs zero
    wire bytes. Because the adjacency is traced, a whole (rounds, N, N)
    schedule runs through ONE jit compile of the step. ``adj=None`` (the
    default, and every pre-existing call site) keeps the static-graph
    program unchanged. A plain custom ``mix_fn`` only needs to accept
    ``adj=`` when dynamic graphs are actually used; the built-in backends
    (core/gossip.make_mix_fn) all do.

    The traced adjacency may be WEIGHTED (the heterogeneity engine,
    experiments/heterogeneity.py): a zero row+column removes a straggling
    or unavailable client from the round (its mixing row collapses to
    e_i, zero wire bytes charged — the accounting binarizes the matrix),
    and a fractional column decays a stale sender's weight before row
    renormalization. Weighted entries need the dense wiring; the permute
    paths read the adjacency as a binary mask.

    ``comm`` (comm/codecs.CommConfig) runs the exchange through a wire
    codec: the transmitted (N, X) slab is encoded, receivers mix the
    decoded values, and (with ``error_feedback=True``) the per-client
    residual rides ``state.ef`` round over round. Requires the packed
    plane for any codec other than the bit-exact ``fp32`` passthrough.
    When ``mix_fn`` came from ``core/gossip.make_mix_fn(comm=...)`` it is
    comm-aware (fused Pallas dequantize+mix, encoded ppermute payloads);
    a plain ``mix_fn`` is wrapped with the reference decode∘mix∘encode.
    ``state.comm_bytes`` keeps accounting LOGICAL bytes (original
    dtypes); the physical wire bytes are the static per-message codec
    ratio times that — reported by the experiment driver.

    With ``pack_spec`` (core/packing.py), ``state.centers`` must be the
    packed (S, N, X) plane (``packing.pack_state``) and the round runs the
    flat engine; ``mix_fn`` then receives a (N, X) array instead of a
    pytree (every backend in core/gossip.make_mix_fn handles both).
    ``model_bytes`` fixes the per-model wire size for comm accounting once
    at build time (it is static per model); when omitted it is derived
    once at first trace — packed runs always account ORIGINAL dtypes via
    the pack spec, so packing never changes reported comm bytes.

    ``sparse`` (core/sparse.SparseConfig) runs the DisPFL composition:
    ``state.mask`` carries one (N, X) binary mask per client, the local
    step trains on the masked support (masked start + masked gradients),
    the exchange is mask-then-encode with a support-renormalized mix
    (num = W·(M⊙Ĉ), den = W·M; each receiver keeps its own value where
    its mask is dead or no active sender covers the coordinate — the
    effective mixing weights are row-stochastic on the active support),
    and a traced RigL prune/regrow updates the mask in-carry every
    ``update_every`` rounds. ``density >= 1.0`` statically routes back to
    the dense code paths (bit-exact parity), the mask riding along
    unchanged. Requires the packed plane; cosine-alignment filtering does
    not compose (the masked weights are support-, not value-, dependent).

    ``donate=True`` returns the step already jitted with
    ``donate_argnums=0``: XLA aliases the state's buffers input→output
    (the (S, N, X) plane — every round's dominant allocation — is updated
    in place across rounds, no per-round copy). The caller must not reuse
    a state it passed in; drive the loop as ``state, m = step(state, d)``.
    """
    optimizer = optimizer or sgd()
    if lr_schedule is None:
        lr_schedule = lambda t: cfg.lr0 * (cfg.lr_decay ** t)  # noqa: E731
    if mix_fn is None:
        mix_fn = lambda c, sel, adj=None: mix(gossip, c, sel, adj=adj)  # noqa: E731

    channel = None
    if comm is not None and comm.codec != "fp32":
        from repro.comm.codecs import exchange, make_channel

        if pack_spec is None:
            raise ValueError(
                f"comm codec {comm.codec!r} requires the packed parameter "
                "plane (pass pack_spec; fp32 is the only pytree-safe codec)"
            )
        channel = make_channel(comm, pack_spec.size)
        if not getattr(mix_fn, "comm_aware", False):
            # a plain (custom) mix_fn gets the reference composition
            base_mix = mix_fn

            def _wrapped_comm_mix(c_sel, s, key, ef, adj=None):
                inner = ((lambda x: base_mix(x, s)) if adj is None
                         else (lambda x: base_mix(x, s, adj=adj)))
                return exchange(channel, c_sel, inner, key, ef)

            _wrapped_comm_mix.comm_aware = True
            mix_fn = _wrapped_comm_mix

    sparse_on = sparse is not None and sparse.enabled
    if sparse_on:
        if pack_spec is None:
            raise ValueError(
                f"sparse training (density={sparse.density}) requires the "
                "packed parameter plane (pass pack_spec)"
            )
        if gossip.cos_align_threshold > -1.0:
            raise ValueError(
                "sparse training does not compose with cosine-alignment "
                "filtering: the masked mixing weights are support-, not "
                "value-, dependent"
            )

    grad_fn = jax.grad(loss_fn)
    sigma = cfg.dp_clip * cfg.dp_noise_multiplier

    # static per-model wire bytes: computed once here (not per trace in the
    # step bodies); the trace-time fallback fills the cell exactly once
    _model_b = [model_bytes if model_bytes is not None
                else (pack_spec.model_bytes if pack_spec is not None
                      else None)]

    def model_b_of(c_sel):
        if _model_b[0] is None:
            _model_b[0] = tree_bytes(c_sel) // cfg.n_clients
        return _model_b[0]

    def dp_sanitize(c_old, c_new, key):
        """Clip the round's update to cfg.dp_clip and add Gaussian noise
        (Wei et al. 2020) — applied per client before the exchange."""
        if cfg.dp_clip <= 0:
            return c_new

        def one(c_o, c_n, k):
            delta = jax.tree.map(lambda a, b: a.astype(jnp.float32)
                                 - b.astype(jnp.float32), c_n, c_o)
            sq = sum(jnp.sum(jnp.square(l)) for l in jax.tree.leaves(delta))
            scale = jnp.minimum(1.0, cfg.dp_clip / jnp.sqrt(sq + 1e-12))
            leaves, treedef = jax.tree.flatten(delta)
            keys = jax.random.split(k, len(leaves))
            sigma = cfg.dp_clip * cfg.dp_noise_multiplier
            noised = [
                l * scale + sigma * jax.random.normal(kk, l.shape)
                for l, kk in zip(leaves, keys)
            ]
            delta = jax.tree.unflatten(treedef, noised)
            return jax.tree.map(
                lambda b, d: (b.astype(jnp.float32) + d).astype(b.dtype),
                c_o, delta)

        n = jax.tree.leaves(c_new)[0].shape[0]
        return jax.vmap(one)(c_old, c_new, jax.random.split(key, n))

    def dp_flat_parts(c_old, c_new, key):
        """Packed-plane DP: ONE flat L2 norm over (N, X) and one fused
        noise draw — no per-leaf walk, no per-leaf key splits. (The noise
        stream therefore differs from the pytree path's per-leaf draws;
        clip-only parity is exact, noisy parity is statistical.) Clip-only
        rounds (sigma == 0) skip the full-plane draw entirely."""
        delta = c_new - c_old
        sq = jnp.sum(jnp.square(delta), axis=-1, keepdims=True)
        scale = jnp.minimum(1.0, cfg.dp_clip / jnp.sqrt(sq + 1e-12))
        noise = (jax.random.normal(key, c_new.shape, c_new.dtype)
                 if sigma > 0 else None)
        return scale, noise

    def _plain_mix(c_sel, s, adj):
        """Static calls keep the exact pre-scenario call shape (and so the
        exact program); a traced adjacency is only threaded when given —
        custom two-arg mix_fns stay valid for static graphs."""
        return mix_fn(c_sel, s) if adj is None else mix_fn(c_sel, s, adj=adj)

    def _channel_mix(c_sel, s, k_comm, ef, adj):
        """The exchange proper: comm-aware (codec + error feedback)
        threading when a compressing channel is on, the plain mix
        otherwise (identical code path and key stream to before)."""
        if channel is None:
            return _plain_mix(c_sel, s, adj), ef
        if adj is None:
            return mix_fn(c_sel, s, k_comm, ef)
        return mix_fn(c_sel, s, k_comm, ef, adj=adj)

    def exchange_packed(plane, c_old, c_new, s, k_dp, k_comm, ef, adj):
        """Steps (2)+(3) on the flat plane: DP sanitize, wire codec,
        Eq. (1) mix, and the write-back into (S, N, X) — all
        single-array ops. When the mix backend exposes a fused
        clip·scale+W·C kernel (Pallas), no cosine filtering is on (the
        weight matrix must not depend on the sanitized values), and no
        codec sits between sanitize and mix, the DP round stays a single
        HBM pass. Returns (plane, ef')."""
        if cfg.dp_clip > 0:
            scale, noise = dp_flat_parts(c_old, c_new, k_dp)
            fused = getattr(mix_fn, "fused_dp", None)
            if (channel is None and fused is not None
                    and gossip.cos_align_threshold <= -1.0):
                c_mixed = (fused(c_old, c_new, scale, noise, sigma, s)
                           if adj is None else
                           fused(c_old, c_new, scale, noise, sigma, s,
                                 adj=adj))
            else:
                c_sel = c_old + scale * (c_new - c_old)
                if noise is not None:
                    c_sel = c_sel + sigma * noise
                c_mixed, ef = _channel_mix(c_sel, s, k_comm, ef, adj)
        else:
            c_mixed, ef = _channel_mix(c_new, s, k_comm, ef, adj)
        with jax.named_scope("fedspd/scatter"):
            plane = _write_rows(plane, s, c_mixed)
        return plane, ef

    # ---------------- sparse (DisPFL) plane machinery ---------------------

    def exchange_sparse(plane, c_old, c_new, s, smask, k_dp, k_comm, ef, adj):
        """Sparse variant of steps (2)+(3): DP sanitize then RE-mask (noise
        must not densify the support), mask-then-encode on the wire, and a
        support-renormalized mix:

            num = W·(M ⊙ Ĉ)    den = W·M
            out = where(M_i ∧ den > 0, num / den, own value)

        Per coordinate the effective weights w_ij·m_jx/den sum to 1 over
        the senders that carry it — row-stochastic on the active support —
        and a receiver's dead coordinates stay untouched (zero). The EF
        residual is masked after every update so dead coordinates never
        accumulate deferred error."""
        if cfg.dp_clip > 0:
            scale, noise = dp_flat_parts(c_old, c_new, k_dp)
            c_sel = c_old + scale * (c_new - c_old)
            if noise is not None:
                c_sel = c_sel + sigma * noise
            c_sel = smask * c_sel
        else:
            c_sel = c_new  # masked start + masked grads => already on support
        w = fedspd_weight_matrix(gossip, s, None, adj=adj)
        colact = column_activity(smask)
        kernel = getattr(mix_fn, "sparse_matmul", None)

        def matmul(w_, v):
            if kernel is None:
                return jnp.einsum(
                    "ij,jx->ix", w_, v,
                    preferred_element_type=jnp.float32)
            return kernel(w_, v, colact)

        if channel is None:
            num = matmul(w, c_sel)
        else:
            dequant = getattr(mix_fn, "sparse_dequant", None)
            fused = dequant is not None and getattr(channel, "fused", False)
            enc, x_hat, ef = channel.encode_stream(
                c_sel, k_comm, ef, need_hat=channel.has_ef or not fused)
            if ef is not None:
                ef = smask * ef
            if fused:
                num = dequant(w, enc, smask)
            else:
                # decoded zeros stay exactly zero for every codec, but the
                # support contract must not hinge on that: re-mask
                num = matmul(w, smask * x_hat)
        den = matmul(w, smask)
        c_mixed = jnp.where(
            jnp.logical_and(smask > 0, den > 0),
            num / jnp.maximum(den, 1e-12), c_sel,
        )
        with jax.named_scope("fedspd/scatter"):
            plane = _write_rows(plane, s, c_mixed)
        return plane, ef

    def dense_grads(c_flat, data, z, s, key):
        """One DENSE gradient pass at the post-update masked parameters —
        RigL's regrow score asks where the loss would move dead
        coordinates hardest. Operates on the flat (N, X) slab via the
        pack-spec boundary; skipped statically for regrow="random"."""
        if cfg.regime == "full":
            bx = client_batches(
                key, data["inputs"], data["targets"], z, s, cfg.batch
            )
            batch = {"x": bx[0], "y": bx[1]}

            def one(f, b):
                return loss_fn(unpack(f, pack_spec), b)

            return jax.vmap(jax.grad(one))(c_flat, batch)

        def one(f, b, m):
            pel = per_example_loss(unpack(f, pack_spec), b)
            return jnp.sum(pel * m) / jnp.maximum(jnp.sum(m), 1.0)

        return jax.vmap(jax.grad(one))(c_flat, data["batch"], data["mask"])

    def sparse_mask_update(state, c_new, data, s):
        """Traced RigL prune/regrow riding the round carry. The key is
        derived via fold_in(state.key, round) WITHOUT consuming the main
        split sequence, so loop and scan engines — and the dense program
        when density >= 1 — see identical key streams."""
        k_mask = jax.random.fold_in(
            jax.random.fold_in(state.key, 0x51AB), state.round
        )
        k_grow, k_batch = jax.random.split(k_mask)
        if sparse.regrow == "rigl":
            g_dense = dense_grads(c_new, data, state.z, s, k_batch)
        else:
            g_dense = jnp.zeros_like(c_new)
        return maybe_update_mask(
            state.mask, c_new, g_dense, k_grow, state.round, sparse
        )

    def assign_batch(centers_i, batch_i):
        """Stream regime: each example of client i's batch to the cluster
        whose center (of client i's S) has the least loss. (B,)"""
        losses = jax.vmap(lambda c: per_example_loss(c, batch_i))(centers_i)
        return jnp.argmin(losses, axis=0)

    def ema_u(u_old, zb):
        """Stream regime: u as an EMA of the batch assignment fractions."""
        u_batch = jax.vmap(
            lambda z_: mixture_coefficients(z_, cfg.n_clusters)
        )(zb)
        return (1 - cfg.u_ema) * u_old + cfg.u_ema * u_batch

    def local_updates(c_sel, data, z, s, key, lr, grad_mask=None):
        """τ SGD steps on the selected centers, cluster-conditional batches.

        ``grad_mask`` (a pytree of {0,1} leaves matching the params) is the
        sparse engine's support projection: gradients are masked every
        step, so a masked start stays on the active support for all τ
        steps — true sparse local training, not mask-at-boundaries."""
        opt_state = jax.vmap(optimizer.init)(c_sel)

        def one_step(carry, k):
            c, opt_s = carry
            if cfg.regime == "full":
                bx = client_batches(
                    k, data["inputs"], data["targets"], z, s, cfg.batch
                )
                batch = {"x": bx[0], "y": bx[1]}
                grads = jax.vmap(grad_fn)(c, batch)
            else:
                # stream: fixed batch, mask examples not in selected cluster
                def masked_loss(params, batch_i, mask_i):
                    pel = per_example_loss(params, batch_i)
                    denom = jnp.maximum(jnp.sum(mask_i), 1.0)
                    return jnp.sum(pel * mask_i) / denom

                grads = jax.vmap(jax.grad(masked_loss))(
                    c, data["batch"], data["mask"]
                )
            if grad_mask is not None:
                grads = jax.tree.map(
                    lambda g, m: g * m.astype(g.dtype), grads, grad_mask
                )
            c, opt_s = jax.vmap(
                lambda g, o, p: optimizer.update(g, o, p, lr)
            )(grads, opt_s, c)
            return (c, opt_s), None

        keys = jax.random.split(key, cfg.tau)
        (c_sel, _), _ = jax.lax.scan(one_step, (c_sel, opt_state), keys)
        return c_sel

    def step_full(state: FedSPDState, data: dict, adj=None):
        key, k_sel, k_local = jax.random.split(state.key, 3)
        lr = lr_schedule(state.round)

        # (1) cluster selection + τ local steps
        with jax.named_scope("fedspd/select"):
            s = select_clusters(k_sel, state.u)
            c_sel = _gather_selected(state.centers, s)
        with jax.named_scope("fedspd/local_step"):
            c_new = local_updates(c_sel, data, state.z, s, k_local, lr)
        key, k_dp = jax.random.split(key)
        c_sel = dp_sanitize(c_sel, c_new, k_dp)

        # (2)+(3) exchange & cluster-matched averaging
        c_mixed = _plain_mix(c_sel, s, adj)
        with jax.named_scope("fedspd/scatter"):
            centers = _scatter_selected(state.centers, s, c_mixed)

        # (4) re-cluster all local data and refresh u
        batch_all = {"x": data["inputs"], "y": data["targets"]}
        with jax.named_scope("fedspd/cluster"):
            z, u = cluster_all_clients(
                per_example_loss, centers, batch_all, cfg.n_clusters,
                chunk=cfg.cluster_chunk,
            )

        comm = state.comm_bytes + round_comm_bytes(
            gossip, s, model_b_of(c_sel), point_to_point=cfg.point_to_point,
            adj=adj,
        )
        new_state = FedSPDState(
            centers=centers, u=u, z=z, round=state.round + 1, key=key,
            comm_bytes=comm, mask=state.mask,
        )
        with jax.named_scope("fedspd/consensus"):
            consensus = _consensus_per_cluster(centers, cfg.n_clusters)
        metrics = {
            "lr": lr,
            "selected": s,
            "consensus": consensus,
            "comm_bytes": comm,
        }
        return new_state, metrics

    def step_stream(state: FedSPDState, batch: dict, adj=None):
        """batch leaves (N, B, ...): this round's fresh per-client data."""
        key, k_sel, k_local = jax.random.split(state.key, 3)
        lr = lr_schedule(state.round)
        with jax.named_scope("fedspd/select"):
            s = select_clusters(k_sel, state.u)
            c_sel = _gather_selected(state.centers, s)

        # per-batch clustering under *current* centers (Step 4, streamed)
        with jax.named_scope("fedspd/cluster"):
            centers_nc = jax.tree.map(lambda l: jnp.swapaxes(l, 0, 1),
                                      state.centers)
            zb = jax.vmap(assign_batch)(centers_nc, batch)  # (N, B)
            mask = (zb == s[:, None]).astype(jnp.float32)

        with jax.named_scope("fedspd/local_step"):
            c_new = local_updates(
                c_sel, {"batch": batch, "mask": mask}, None, s, k_local, lr
            )
        key, k_dp = jax.random.split(key)
        c_sel = dp_sanitize(c_sel, c_new, k_dp)
        c_mixed = _plain_mix(c_sel, s, adj)
        with jax.named_scope("fedspd/scatter"):
            centers = _scatter_selected(state.centers, s, c_mixed)

        with jax.named_scope("fedspd/cluster"):
            u = ema_u(state.u, zb)

        comm = state.comm_bytes + round_comm_bytes(
            gossip, s, model_b_of(c_sel), point_to_point=cfg.point_to_point,
            adj=adj,
        )
        new_state = FedSPDState(
            centers=centers, u=u, z=state.z, round=state.round + 1, key=key,
            comm_bytes=comm, mask=state.mask,
        )
        with jax.named_scope("fedspd/consensus"):
            consensus = _consensus_per_cluster(centers, cfg.n_clusters)
        metrics = {
            "lr": lr,
            "selected": s,
            "consensus": consensus,
            "comm_bytes": comm,
        }
        return new_state, metrics

    # ---------------- packed (S, N, X) parameter-plane engine -------------

    def step_full_packed(state: FedSPDState, data: dict, adj=None):
        plane = state.centers                       # (S, N, X)
        key, k_sel, k_local = jax.random.split(state.key, 3)
        lr = lr_schedule(state.round)

        # (1) cluster selection + τ local steps. gather = ONE dynamic
        # slice on the plane; the local-SGD scan needs model structure, so
        # parameters take pytree form only inside this scope.
        with jax.named_scope("fedspd/select"):
            s = select_clusters(k_sel, state.u)
            c_old = _select_rows(plane, s)          # (N, X)
            if sparse_on:
                # support applies at gather: rows of OTHER clusters may
                # carry coordinates from an older mask; the current mask
                # projects
                c_old = state.mask * c_old
        with jax.named_scope("fedspd/local_step"):
            grad_mask = (unpack(state.mask, pack_spec) if sparse_on
                         else None)
            c_new_tree = local_updates(
                unpack(c_old, pack_spec), data, state.z, s, k_local, lr,
                grad_mask=grad_mask,
            )
            c_new = pack(c_new_tree, pack_spec)
        if channel is None:
            key, k_dp = jax.random.split(key)
            k_comm = None
        else:
            key, k_dp, k_comm = jax.random.split(key, 3)

        # (2)+(3) flat sanitize + wire codec + mix + scatter
        if sparse_on:
            new_mask = sparse_mask_update(state, c_new, data, s)
            plane, ef = exchange_sparse(plane, c_old, c_new, s, state.mask,
                                        k_dp, k_comm, state.ef, adj)
        else:
            new_mask = state.mask
            plane, ef = exchange_packed(plane, c_old, c_new, s, k_dp, k_comm,
                                        state.ef, adj)

        # (4) re-cluster: the forward pass needs model structure again
        batch_all = {"x": data["inputs"], "y": data["targets"]}
        with jax.named_scope("fedspd/cluster"):
            z, u = cluster_all_clients(
                per_example_loss, unpack(plane, pack_spec), batch_all,
                cfg.n_clusters, chunk=cfg.cluster_chunk,
            )

        comm = state.comm_bytes + round_comm_bytes(
            gossip, s, model_b_of(None), point_to_point=cfg.point_to_point,
            adj=adj,
        )
        new_state = FedSPDState(
            centers=plane, u=u, z=z, round=state.round + 1, key=key,
            comm_bytes=comm, ef=ef, mask=new_mask,
        )
        with jax.named_scope("fedspd/consensus"):
            consensus = _consensus_per_cluster_flat(plane)
        metrics = {
            "lr": lr,
            "selected": s,
            "consensus": consensus,
            "comm_bytes": comm,
        }
        return new_state, metrics

    def step_stream_packed(state: FedSPDState, batch: dict, adj=None):
        plane = state.centers                       # (S, N, X)
        key, k_sel, k_local = jax.random.split(state.key, 3)
        lr = lr_schedule(state.round)
        with jax.named_scope("fedspd/select"):
            s = select_clusters(k_sel, state.u)
            c_old = _select_rows(plane, s)          # (N, X)
            if sparse_on:
                c_old = state.mask * c_old

        # per-batch clustering under *current* centers (model structure)
        with jax.named_scope("fedspd/cluster"):
            centers_nc = jax.tree.map(
                lambda l: jnp.swapaxes(l, 0, 1), unpack(plane, pack_spec)
            )
            zb = jax.vmap(assign_batch)(centers_nc, batch)  # (N, B)
            mask = (zb == s[:, None]).astype(jnp.float32)

        with jax.named_scope("fedspd/local_step"):
            grad_mask = (unpack(state.mask, pack_spec) if sparse_on
                         else None)
            c_new_tree = local_updates(
                unpack(c_old, pack_spec), {"batch": batch, "mask": mask},
                None, s, k_local, lr, grad_mask=grad_mask,
            )
            c_new = pack(c_new_tree, pack_spec)
        if channel is None:
            key, k_dp = jax.random.split(key)
            k_comm = None
        else:
            key, k_dp, k_comm = jax.random.split(key, 3)
        if sparse_on:
            new_mask = sparse_mask_update(
                state, c_new, {"batch": batch, "mask": mask}, s
            )
            plane, ef = exchange_sparse(plane, c_old, c_new, s, state.mask,
                                        k_dp, k_comm, state.ef, adj)
        else:
            new_mask = state.mask
            plane, ef = exchange_packed(plane, c_old, c_new, s, k_dp, k_comm,
                                        state.ef, adj)

        with jax.named_scope("fedspd/cluster"):
            u = ema_u(state.u, zb)

        comm = state.comm_bytes + round_comm_bytes(
            gossip, s, model_b_of(None), point_to_point=cfg.point_to_point,
            adj=adj,
        )
        new_state = FedSPDState(
            centers=plane, u=u, z=state.z, round=state.round + 1, key=key,
            comm_bytes=comm, ef=ef, mask=new_mask,
        )
        with jax.named_scope("fedspd/consensus"):
            consensus = _consensus_per_cluster_flat(plane)
        metrics = {
            "lr": lr,
            "selected": s,
            "consensus": consensus,
            "comm_bytes": comm,
        }
        return new_state, metrics

    if pack_spec is not None:
        step = step_full_packed if cfg.regime == "full" else step_stream_packed
    else:
        step = step_full if cfg.regime == "full" else step_stream
    return jax.jit(step, donate_argnums=0) if donate else step


def _consensus_per_cluster(centers: PyTree, s_clusters: int) -> jnp.ndarray:
    ds = []
    for s_idx in range(s_clusters):
        c_s = jax.tree.map(lambda l: l[s_idx], centers)
        ds.append(consensus_distance(c_s))
    return jnp.stack(ds)


def _consensus_per_cluster_flat(plane: jnp.ndarray) -> jnp.ndarray:
    """Theorem 5.10's E_t per cluster as ONE flat reduction over the packed
    (S, N, X) plane — no per-cluster/per-leaf python loop."""
    p32 = plane.astype(jnp.float32)
    mean = jnp.mean(p32, axis=1, keepdims=True)
    return jnp.sum(jnp.square(p32 - mean), axis=(1, 2)) / plane.shape[1]


# --------------------------------------------------------------------------
# Final phase (Algorithm 1, FINALPHASE)
# --------------------------------------------------------------------------


def personalize(state: FedSPDState,
                pack_spec: Optional[PackSpec] = None) -> PyTree:
    """Eq. (2): x_i = Σ_s u_{i,s} c_{i,s}. Returns leaves (N, ...).

    Packed states collapse to ONE weighted contraction over the plane
    (`(N, S)·(S, N, X) -> (N, X)`), unpacked to pytree form only here —
    the API boundary."""
    if pack_spec is not None:
        plane = state.centers  # (S, N, X)
        mixed = jnp.einsum(
            "ns,snx->nx", state.u.astype(plane.dtype), plane
        )
        return unpack(mixed, pack_spec)
    centers_nc = jax.tree.map(lambda l: jnp.swapaxes(l, 0, 1), state.centers)

    def one(centers_i, u_i):
        return tree_weighted_sum(centers_i, u_i)

    return jax.vmap(one)(centers_nc, state.u)


def final_phase(
    state: FedSPDState,
    loss_fn: Callable,
    data: dict,  # leaves (N, M, ...)
    cfg: FedSPDConfig,
    optimizer: Optimizer = None,
    lr: float | None = None,
    pack_spec: Optional[PackSpec] = None,
) -> PyTree:
    """Aggregate (Eq. 2) then τ_final local epochs on ALL local data —
    communication-free personalization. Returns personalized params (N, ...)."""
    optimizer = optimizer or sgd()
    params = personalize(state, pack_spec)
    lr = lr if lr is not None else cfg.lr0 * cfg.final_lr_scale * (
        cfg.lr_decay ** state.round
    )
    grad_fn = jax.grad(loss_fn)
    opt_state = jax.vmap(optimizer.init)(params)

    def one_step(carry, k):
        p, opt_s = carry
        bx, by = client_uniform_batches(k, data["inputs"], data["targets"],
                                        cfg.batch)
        grads = jax.vmap(grad_fn)(p, {"x": bx, "y": by})
        p, opt_s = jax.vmap(lambda g, o, pp: optimizer.update(g, o, pp, lr))(
            grads, opt_s, p
        )
        return (p, opt_s), None

    # tau_final counts EPOCHS over the full local dataset (paper Table 1:
    # "Number of epochs for the final phase"), not SGD steps
    m = jax.tree.leaves(data)[0].shape[1]
    steps = cfg.tau_final * max(1, m // cfg.batch)
    keys = jax.random.split(state.key, steps)
    (params, _), _ = jax.lax.scan(one_step, (params, opt_state), keys)
    return params
