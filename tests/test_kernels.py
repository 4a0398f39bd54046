"""Pallas kernel validation: shape/dtype sweeps vs the pure-jnp oracles in
kernels/ref.py, executed with interpret=True on CPU."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import ops, ref

KEY = jax.random.PRNGKey(0)


_SLOW = pytest.mark.slow  # full interpret-mode sweeps run in the full lane;
# the first combo of each sweep stays in the fast lane as a smoke case


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize(
    "b,lq,lkv,hq,hkv,hd,window",
    [
        (2, 256, 256, 4, 2, 64, None),   # GQA causal
        pytest.param(1, 256, 256, 4, 4, 64, 128, marks=_SLOW),  # MHA window
        pytest.param(2, 128, 128, 8, 2, 32, None, marks=_SLOW),  # small hd
        pytest.param(1, 512, 512, 2, 1, 64, 256, marks=_SLOW),  # kv=1+window
        pytest.param(1, 384, 384, 4, 4, 128, None, marks=_SLOW),  # non-pow2
    ],
)
def test_flash_attention_sweep(b, lq, lkv, hq, hkv, hd, window, dtype):
    ks = jax.random.split(KEY, 3)
    q = jax.random.normal(ks[0], (b, lq, hq, hd), dtype)
    k = jax.random.normal(ks[1], (b, lkv, hkv, hd), dtype)
    v = jax.random.normal(ks[2], (b, lkv, hkv, hd), dtype)
    out = ops.flash_attention(q, k, v, causal=True, window=window)
    want = ref.flash_attention_ref(q, k, v, causal=True, window=window)
    atol = 2e-5 if dtype == jnp.float32 else 2e-2
    np.testing.assert_allclose(
        np.asarray(out, np.float32), np.asarray(want, np.float32), atol=atol
    )


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize(
    "b,l,h,g,p,n,chunk",
    [
        (1, 128, 8, 2, 32, 16, 64),
        pytest.param(2, 256, 4, 1, 64, 64, 128, marks=_SLOW),
        pytest.param(2, 256, 4, 4, 64, 128, 128, marks=_SLOW),
        pytest.param(1, 512, 2, 1, 64, 64, 128, marks=_SLOW),
    ],
)
def test_ssd_scan_sweep(b, l, h, g, p, n, chunk, dtype):
    ks = jax.random.split(KEY, 5)
    x = jax.random.normal(ks[0], (b, l, h, p), dtype)
    dt = (jax.nn.softplus(jax.random.normal(ks[1], (b, l, h))) * 0.1).astype(dtype)
    A = -jnp.exp(jax.random.uniform(ks[2], (h,)))
    Bm = jax.random.normal(ks[3], (b, l, g, n), dtype)
    Cm = jax.random.normal(ks[4], (b, l, g, n), dtype)
    y, s = ops.ssd_scan(x, dt, A, Bm, Cm, chunk=chunk)
    Bh = jnp.repeat(Bm, h // g, axis=2)
    Ch = jnp.repeat(Cm, h // g, axis=2)
    yr, sr = ref.ssd_scan_ref(x, dt, A, Bh, Ch)
    atol = 2e-3 if dtype == jnp.float32 else 5e-2
    np.testing.assert_allclose(
        np.asarray(y, np.float32), np.asarray(yr, np.float32), atol=atol,
        rtol=atol)
    np.testing.assert_allclose(np.asarray(s), np.asarray(sr), atol=atol,
                               rtol=atol)


def test_ssd_scan_initial_state():
    ks = jax.random.split(KEY, 6)
    b, l, h, p, n = 1, 128, 2, 32, 16
    x = jax.random.normal(ks[0], (b, l, h, p))
    dt = jax.nn.softplus(jax.random.normal(ks[1], (b, l, h))) * 0.1
    A = -jnp.exp(jax.random.uniform(ks[2], (h,)))
    Bm = jax.random.normal(ks[3], (b, l, h, n))
    Cm = jax.random.normal(ks[4], (b, l, h, n))
    s0 = jax.random.normal(ks[5], (b, h, p, n))
    y, s = ops.ssd_scan(x, dt, A, Bm, Cm, chunk=64, initial_state=s0)
    yr, sr = ref.ssd_scan_ref(x, dt, A, Bm, Cm, initial_state=s0)
    np.testing.assert_allclose(np.asarray(y), np.asarray(yr), atol=2e-3,
                               rtol=2e-3)


@pytest.mark.parametrize("n_clients", [4, 8, 16])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_gossip_mix_sweep(n_clients, dtype):
    key = jax.random.PRNGKey(n_clients)
    w = jax.nn.softmax(jax.random.normal(key, (n_clients, n_clients)), axis=1)
    tree = {
        "a": jax.random.normal(key, (n_clients, 33, 7), dtype),
        "b": jax.random.normal(key, (n_clients, 5000), dtype),
        "c": jax.random.normal(key, (n_clients,), dtype),
    }
    out = ops.gossip_mix(w, tree)
    want = ref.gossip_mix_ref(w, tree)
    for k in tree:
        np.testing.assert_allclose(
            np.asarray(out[k], np.float32), np.asarray(want[k], np.float32),
            atol=1e-2 if dtype == jnp.bfloat16 else 1e-5)


def test_gossip_mix_matches_fedspd_dense_path():
    """Kernel applied with the FedSPD Eq. (1) weight matrix == mix_dense."""
    from repro.core.gossip import GossipSpec, fedspd_weight_matrix, mix_dense
    from repro.graphs.topology import make_graph

    g = make_graph("er", 8, 3.0, seed=0)
    spec = GossipSpec.from_graph(g)
    key = jax.random.PRNGKey(3)
    s = jax.random.randint(key, (8,), 0, 2)
    tree = {"w": jax.random.normal(key, (8, 40))}
    wmat = fedspd_weight_matrix(spec, s)
    out = ops.gossip_mix(wmat, tree)
    want = mix_dense(spec, tree, s)
    np.testing.assert_allclose(np.asarray(out["w"]), np.asarray(want["w"]),
                               atol=1e-5)


def test_moe_dispatch_modes_agree():
    """sort == cumsum exactly; grouped == global when capacity is generous
    (per-sequence grouping only changes the drop pattern)."""
    import jax
    import jax.numpy as jnp
    from repro.models.moe import apply_moe, init_moe

    key = jax.random.PRNGKey(0)
    p = init_moe(key, 32, 64, 8, "silu", jnp.float32)
    x = jax.random.normal(key, (4, 16, 32))
    o_cum, _ = apply_moe(p, x, top_k=2, capacity_factor=8.0, act="silu",
                         dispatch="cumsum")
    o_sort, _ = apply_moe(p, x, top_k=2, capacity_factor=8.0, act="silu",
                          dispatch="sort")
    o_grp, _ = apply_moe(p, x, top_k=2, capacity_factor=8.0, act="silu",
                         dispatch="grouped")
    np.testing.assert_allclose(np.asarray(o_cum), np.asarray(o_sort), atol=1e-5)
    np.testing.assert_allclose(np.asarray(o_cum), np.asarray(o_grp), atol=1e-5)


@pytest.mark.parametrize("x,x_block", [(13, 8), (5000, 2048), (7, 32), (2048, 2048)])
def test_gossip_mix_flat_padding(x, x_block):
    """X not divisible by x_block exercises the ragged trailing block
    (Pallas edge masking — no host-side pad/crop copies) and x_block > X
    exercises the block clamp; both must equal the dense W@C."""
    from repro.kernels.gossip_mix import gossip_mix_flat

    key = jax.random.PRNGKey(x)
    n = 8
    w = jax.nn.softmax(jax.random.normal(key, (n, n)), axis=1)
    c = jax.random.normal(key, (n, x), jnp.float32)
    out = gossip_mix_flat(w, c, x_block=x_block, interpret=True)
    assert out.shape == (n, x)
    np.testing.assert_allclose(np.asarray(out), np.asarray(w @ c), atol=1e-5)


@pytest.mark.parametrize("x,qblock", [(1000, 2), (132, 4)])
def test_mixture_dequant4_slab_layout_matches_decode(x, qblock):
    """The int4 serve kernel reads the plane re-laid by
    ``int4_slab_layout`` (per slab: low nibbles the first half of the
    columns, high nibbles the second); across several slabs and a ragged
    tail it equals U @ decode(plane) of the paired-nibble artifact."""
    from repro.comm.codecs import int4_pack, int4_unpack
    from repro.kernels.gossip_mix import (
        int4_slab_layout,
        int4_slab_width,
        mixture_mix_dequant4,
    )

    s, b = 3, 5
    ks = jax.random.split(jax.random.PRNGKey(x), 3)
    q = jax.random.randint(ks[0], (s, x), -8, 8, jnp.int8)
    scale = jax.random.uniform(ks[1], (s, x // qblock), jnp.float32)
    u = jax.random.uniform(ks[2], (b, s), jnp.float32)
    packed = int4_pack(q)
    slabs, sc = int4_slab_layout(packed, scale, qblock=qblock)
    assert slabs.shape[1] * 2 % int4_slab_width(qblock) == 0
    out = mixture_mix_dequant4(u, slabs, sc, qblock=qblock, interpret=True)
    plane = (int4_unpack(packed, x).astype(jnp.float32)
             * jnp.repeat(scale, qblock, axis=1))
    np.testing.assert_allclose(np.asarray(out[:, :x]),
                               np.asarray(u @ plane), atol=1e-5)


def test_pallas_mix_fn_matches_reference_mix():
    """core/gossip.make_mix_fn('pallas') — the FedSPD gossip fast path —
    equals core/gossip.mix to fp32 tolerance on arbitrary trees/selections."""
    from repro.core.gossip import GossipSpec, make_mix_fn, mix
    from repro.graphs.topology import make_graph

    for seed in range(3):
        g = make_graph("er", 10, 4.0, seed=seed)
        spec = GossipSpec.from_graph(g, mode="dense")
        key = jax.random.PRNGKey(seed)
        tree = {
            "a": jax.random.normal(key, (10, 5, 3)),
            "b": jax.random.normal(key, (10, 17)),
            "c": jax.random.normal(key, (10,)),
        }
        s = jax.random.randint(key, (10,), 0, 2)
        ref_out = mix(spec, tree, s)
        pallas_out = make_mix_fn(spec, backend="pallas")(tree, s)
        for k in tree:
            np.testing.assert_allclose(
                np.asarray(pallas_out[k]), np.asarray(ref_out[k]), atol=1e-5)


def test_make_mix_fn_rejects_unknown_backend():
    from repro.core.gossip import GossipSpec, make_mix_fn
    from repro.graphs.topology import make_graph

    spec = GossipSpec.from_graph(make_graph("er", 6, 3.0, seed=0))
    with pytest.raises(ValueError, match="unknown gossip backend"):
        make_mix_fn(spec, backend="cuda")


# --- slab planning of the X-tiled mixes (compiled plans, traced on CPU) ----

X_WHISPER = 97_271_808   # whisper-base parameter count, the train cell's X


def _compiled_plan(fn, shapes):
    """Grid and ``(block sizes, array aval)`` of each blocked operand of
    the one ``pallas_call`` that ``fn`` traces to with ``interpret=False``
    (traced only: nothing is lowered or run)."""
    args = [jax.ShapeDtypeStruct(s, d) for s, d in shapes]
    eqns = [e for e in jax.make_jaxpr(fn)(*args).jaxpr.eqns
            if e.primitive.name == "pallas_call"]
    assert len(eqns) == 1
    gmap = eqns[0].params["grid_mapping"]
    blocks = [(tuple(getattr(b, "block_size", b) for b in bm.block_shape),
               bm.array_aval) for bm in gmap.block_mappings]
    return gmap.grid, blocks


def _slab_vmem_bytes(blocks):
    """The planner's VMEM estimate from the kernel's own blocks: each
    operand tiled along X (block narrower than its array), rows padded to
    the sublane tile, lanes to 128, item size, two buffers."""
    total = 0
    for shape, aval in blocks:
        if shape[-1] == aval.shape[-1]:
            continue                       # W, clip scales: not slab-wide
        size = aval.dtype.itemsize
        tile = 32 // size
        rows = -(-shape[-2] // tile) * tile
        lanes = -(-shape[-1] // 128) * 128
        total += int(np.prod(shape[:-2])) * rows * lanes * size * 2
    return total


def _tiled_mix_cases(n):
    """name -> (fn, shapes, narrowest slab Mosaic allows) for every X-tiled
    kernel of kernels/gossip_mix at n clients and the train cell's X."""
    from repro.kernels import gossip_mix as gm

    f32, i8, qb, x = jnp.float32, jnp.int8, 256, X_WHISPER
    xp = -(-x // qb) * qb
    w, plane = ((n, n), f32), ((n, x), f32)
    quant = [w, ((n, xp), i8), ((n, xp // qb), f32)]
    return {
        "flat": (lambda w_, c: gm.gossip_mix_flat(w_, c, interpret=False),
                 [w, plane], 128),
        "stack": (lambda w_, c: gm.gossip_mix_stack(w_, c, interpret=False),
                  [w, ((2, n, x), f32)], 128),
        "sparse": (lambda w_, c, a: gm.gossip_mix_sparse(
            w_, c, a, interpret=False), [w, plane, ((x,), f32)], 128),
        "fused_dp": (lambda w_, co, cn, sc, nz: gm.gossip_mix_fused_dp(
            w_, co, cn, sc, nz, 0.5, interpret=False),
            [w, plane, plane, ((n, 1), f32), plane], 128),
        "fused_dp_clip": (lambda w_, co, cn, sc: gm.gossip_mix_fused_dp(
            w_, co, cn, sc, None, 0.0, interpret=False),
            [w, plane, plane, ((n, 1), f32)], 128),
        "dequant": (lambda w_, q, sc: gm.gossip_mix_dequant(
            w_, q, sc, qblock=qb, interpret=False), quant, 128 * qb),
        "dequant_masked": (lambda w_, q, sc, m: gm.gossip_mix_dequant_masked(
            w_, q, sc, m, qblock=qb, interpret=False), quant + [plane],
            128 * qb),
        "serve_dequant": (lambda u, q, sc: gm.gossip_mix_dequant(
            u, q, sc, qblock=64, interpret=False),
            [((32, n), f32), ((n, -(-x // 64) * 64), i8),
             ((n, -(-x // 64)), f32)], 128 * 64),
    }


@pytest.mark.parametrize("name,n", [
    (name, n) for name in sorted(_tiled_mix_cases(2))
    # an N=128 quantized slab is past the budget at its narrowest
    for n in ((2, 4) if "dequant" in name else (2, 4, 128))])
def test_planned_slab_fits_vmem_budget(name, n):
    """Every X-tiled mix plans a slab whose double-buffered operands fit
    the planner's VMEM budget; a quantized kernel may exceed it only at
    the narrowest slab Mosaic allows (128 scale blocks)."""
    from repro.kernels.gossip_mix import _SLAB_VMEM_BYTES

    fn, shapes, narrowest = _tiled_mix_cases(n)[name]
    _, blocks = _compiled_plan(fn, shapes)
    width = max(s[-1] for s, a in blocks if s[-1] < a.shape[-1]
                and a.shape[-1] >= X_WHISPER)
    assert width % 128 == 0
    assert (_slab_vmem_bytes(blocks) <= _SLAB_VMEM_BYTES
            or width == narrowest)


@pytest.mark.parametrize("n,x_block,width,max_steps", [
    (2, None, 32_768, 3_000),      # the train cell: wide slab, few steps
    (128, None, 2_048, None),      # large N keeps its 2048 columns
    (2, 2_048, 2_048, None),       # an explicit x_block is honoured
    (2, 4_096, 4_096, None),
])
def test_flat_mix_plan(n, x_block, width, max_steps):
    """gossip_mix_flat's compiled plan at the train cell's X: the default
    slab is sized by bytes, and an explicit x_block wins."""
    from repro.kernels.gossip_mix import gossip_mix_flat

    (steps,), blocks = _compiled_plan(
        lambda w, c: gossip_mix_flat(w, c, x_block=x_block,
                                     interpret=False),
        [((n, n), jnp.float32), ((n, X_WHISPER), jnp.float32)])
    w_block = [] if n <= 8 else [(n, n)]     # N <= 8: W rides in SMEM
    assert [s for s, _ in blocks] == w_block + [(n, width), (n, width)]
    assert steps == -(-X_WHISPER // width)
    if max_steps:
        assert steps <= max_steps


@pytest.mark.parametrize("n", [2, 8, 16])   # VPU, VPU, MXU contraction
def test_flat_mix_matches_mix_dense(n):
    """gossip_mix_flat equals core/gossip.mix_dense on either contraction
    path (VPU for N ≤ 8, MXU above): a weighted adjacency gives a
    row-stochastic W of unequal weights, and 1000 columns at x_block 384
    end in a ragged trailing block."""
    from repro.core.gossip import GossipSpec, fedspd_weight_matrix, mix_dense
    from repro.graphs.topology import make_graph
    from repro.kernels.gossip_mix import gossip_mix_flat

    spec = GossipSpec.from_graph(make_graph("complete", n, n - 1, seed=n))
    ks = jax.random.split(jax.random.PRNGKey(n), 3)
    adj = jax.random.uniform(ks[0], (n, n), jnp.float32, 0.1, 1.0)
    s = jax.random.randint(ks[1], (n,), 0, 2)
    c = jax.random.normal(ks[2], (n, 1000), jnp.float32)
    w = fedspd_weight_matrix(spec, s, adj=adj)
    assert len(np.unique(np.round(np.asarray(w), 6))) > 3
    out = gossip_mix_flat(w, c, x_block=384, interpret=True)
    want = mix_dense(spec, {"c": c}, s, adj=adj)["c"]
    np.testing.assert_allclose(np.asarray(out), np.asarray(want), atol=1e-6)
