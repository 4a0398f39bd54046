"""Ahead-of-time Mosaic compiles of the main-path Pallas kernels for a
described TPU v5e chip, at whisper-base width (X = 97,271,808 parameters).

Nothing runs: each test lowers one kernel with ``interpret=False`` against
shape-only arguments placed on a described (not attached) chip and asks
the TPU compiler for the executable. That catches what interpret mode
cannot — blocks that break the (8, 128) tiling rule, lane layouts Mosaic
cannot lower, VMEM overruns, programs larger than HBM — at no chip time.
The topology is described inside a fixture (never at import time), so
every test worker collects the same tests and only the worker that runs
this file loads the TPU compiler.
"""
import os
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.fedspd import _write_rows
from repro.kernels import gossip_mix as gm

X = 97_271_808   # whisper-base parameter count (configs/whisper_base.py)
N = 4            # clients
S = 2            # clusters
QB = 256         # train codec block (launch/train --codec-block)
B = 32           # serve request batch
QB_SERVE = 64    # serve qblock of the compile cases (launch/train exports
                 # with its codec block, 256 by default: see the qb256 case)


@pytest.fixture(scope="module")
def one_chip():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler / libtpu on this host
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a described-chip executable cannot be read back without the chip:
    # keep these compiles out of any persistent cache
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield jax.sharding.SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)


def _xp(qblock):
    return -(-X // qblock) * qblock


def _fp32_cases(n):
    """The fp32 X-tiled mixes at n clients: name -> (fn, shapes)."""
    f32 = jnp.float32
    w = ((n, n), f32)
    plane = ((n, X), f32)
    return {
        "flat": (lambda w_, c: gm.gossip_mix_flat(w_, c, interpret=False),
                 [w, plane]),
        "stack": (lambda w_, c: gm.gossip_mix_stack(w_, c, interpret=False),
                  [w, ((S, n, X), f32)]),
        "sparse": (lambda w_, c, a: gm.gossip_mix_sparse(
            w_, c, a, interpret=False), [w, plane, ((X,), f32)]),
        "fused_dp": (lambda w_, co, cn, sc, nz: gm.gossip_mix_fused_dp(
            w_, co, cn, sc, nz, 0.5, interpret=False),
            [w, plane, plane, ((n, 1), f32), plane]),
    }


def _cases():
    """name -> (kernel fn taking shape args, [(shape, dtype), ...]): the
    fp32 mixes at N clients and, as ``<name>_n2``, at the train cell's 2
    (the widest slab the planner gives), and the quantized kernels."""
    f32, i8 = jnp.float32, jnp.int8
    w = ((N, N), f32)
    plane = ((N, X), f32)
    xp = _xp(QB)
    xs = _xp(QB_SERVE)

    def int4_case(qb):
        width = gm.int4_slab_width(qb)
        xk = -(-_xp(qb) // width) * width
        return (lambda u, p, sc: gm.mixture_mix_dequant4(
            u, p, sc, qblock=qb, interpret=False),
            [((B, S), f32), ((S, xk // 2), jnp.uint8),
             ((S, xk // qb), f32)])

    return {
        **_fp32_cases(N),
        **{f"{k}_n2": v for k, v in _fp32_cases(2).items()},
        "dequant": (lambda w_, q, sc: gm.gossip_mix_dequant(
            w_, q, sc, qblock=QB, interpret=False),
            [w, ((N, xp), i8), ((N, xp // QB), f32)]),
        "dequant_masked": (lambda w_, q, sc, m: gm.gossip_mix_dequant_masked(
            w_, q, sc, m, qblock=QB, interpret=False),
            [w, ((N, xp), i8), ((N, xp // QB), f32), plane]),
        "mixture_dequant4": int4_case(QB_SERVE),
        "mixture_dequant4_qb256": int4_case(QB),
        "serve_dequant_int8": (lambda u, q, sc: gm.gossip_mix_dequant(
            u, q, sc, qblock=QB_SERVE, interpret=False),
            [((B, S), f32), ((S, xs), i8), ((S, xs // QB_SERVE), f32)]),
    }


CASES = sorted(_cases())


@pytest.mark.parametrize("name", CASES)
def test_kernel_compiles_for_v5e(one_chip, name):
    fn, shapes = _cases()[name]
    args = [jax.ShapeDtypeStruct(s, d, sharding=one_chip) for s, d in shapes]
    compiled = jax.jit(fn).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()


def test_train_cell_mix_is_one_kernel_without_copies_on_v5e(one_chip):
    """At the train cell's N=2 the fp32 exchange compiles to one Mosaic
    call on its wide slab, with no copy of the (2, X) plane around it."""
    fn, shapes = _cases()["flat_n2"]
    args = [jax.ShapeDtypeStruct(s, d, sharding=one_chip) for s, d in shapes]
    text = jax.jit(fn).lower(*args).compile().as_text()
    assert text.count("tpu_custom_call") == 1
    assert not re.search(rf"= f32\[2,{X}\]\S* copy\(", text)


def test_int4_slab_layout_shapes_match_kernel():
    """The serve path re-lays the artifact's paired-nibble plane before
    the kernel; the re-laid shapes are the ones compiled above."""
    xs = _xp(QB_SERVE)
    packed = np.zeros((S, xs // 2), np.uint8)
    scales = np.zeros((S, xs // QB_SERVE), np.float32)
    out = gm.int4_slab_layout(packed, scales, qblock=QB_SERVE)
    _, shapes = _cases()["mixture_dequant4"]
    assert [o.shape for o in out] == [s for s, _ in shapes[1:]]


def test_plane_write_back_is_in_place_on_v5e(one_chip):
    """The round step's write-back of the mixed rows into the donated
    (S, N, X) plane compiles to one select over the plane in place: no
    loop, no scatter, and no plane-sized copy."""
    n = 2
    args = [jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)
            for shape, dtype in [((S, n, X), jnp.float32), ((n,), jnp.int32),
                                 ((n, X), jnp.float32)]]
    text = jax.jit(_write_rows, donate_argnums=0).lower(
        *args).compile().as_text()
    assert "input_output_alias={ {}: (0, {}" in text
    assert not re.search(r"\bwhile\(|\bscatter\(", text)
    assert not re.search(rf"= f32\[{S},{n},{X}\]\S* copy\(", text)
