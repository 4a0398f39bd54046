"""Pallas TPU kernel for FedSPD's cluster-matched gossip mix C ← W·C.

TARGET: TPU v5e. The mixing weight matrix W (N×N, row-stochastic, built per
round from the adjacency and this round's cluster selections — Eq. (1)) is
tiny (N ≤ a few hundred clients → ≤ 0.25 MB fp32) and is kept whole in VMEM
for every grid step. The flattened parameter matrix C (N, X) with X up to
tens of billions is tiled along X: grid = (n_x_blocks,), each step loads a
(N, x_block) slab, does one (N×N)·(N×x_block) MXU matmul, and writes the
mixed slab. A plane of at most 8 rows (one fp32 sublane tile) mixes on the
VPU instead, as fp32 multiply-adds with W read as scalars from SMEM: at
K = N ≤ 8 the HIGHEST-precision matmul, not HBM, sets the pace (on a v5e,
(2, 97,271,808) fp32 takes at least 6.6 ms on the MXU at any slab width,
2.4 ms on the VPU). The slab is sized in bytes (``_plan_blocks``): every
slab-wide operand, the output included, with its rows padded to the dtype's
sublane tile and two buffers each, shares a fixed VMEM budget. Each grid step has a
fixed cost, so the slab is as wide as that budget allows: N=128 fp32 gets
2048 columns, N=2 fp32 32,768 (a slab at 2048 columns moves only 32 KiB at
N=2, and the step's overhead, not HBM, then sets the pace). The matmul
K-dim = N is zero-padded to 8/128 alignment by Mosaic. Interpret mode (CPU
validation) defaults to one whole-X block instead — there is no VMEM to
respect and each grid step costs ~100 µs of interpreter overhead.

Every entry point takes ``interpret=None``: Mosaic on a TPU backend, the
interpreter elsewhere (``repro.kernels.resolve_interpret``). Mosaic tiles
each block's last two dims by (8, 128) unless they span the whole array,
so the quantized kernels size their slabs to 128 scale blocks
(``_plan_quant_blocks``), and per-slab activity bits ride as scalar
prefetch operands (SMEM) instead of (1, 1) VMEM blocks.

This fuses FedSPD's neighbor averaging into a single streaming pass over
the parameters — the HBM-bound ideal (read C once, write C once).

Validated on CPU via interpret=True against core/gossip.mix_dense, and
compiled for a described v5e chip by tests/test_tpu_compile.py.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels import resolve_interpret


def _mxu(a, b):
    """(M, K)·(K, cols) with fp32 operands and fp32 accumulation. The fp32
    contract precision is requested explicitly rather than left to the
    compiler's default: the mix is specified as an fp32 average."""
    return jax.lax.dot_general(
        a, b, (((1,), (0,)), ((), ())),
        precision=jax.lax.Precision.HIGHEST,
        preferred_element_type=jnp.float32,
    )


def _mix_kernel(w_ref, c_ref, o_ref):
    w = w_ref[...].astype(jnp.float32)       # (N, N)
    c = c_ref[...].astype(jnp.float32)       # (N, x_block)
    o_ref[...] = _mxu(w, c).astype(o_ref.dtype)


# VMEM for the slab-wide operands of one grid step, two buffers each
# (Pallas double-buffers every blocked operand): 1 MiB a buffer for the
# two-operand fp32 mix at N=128, which keeps its 2048 columns.
_SLAB_VMEM_BYTES = 4 << 20


def _col_bytes(*slabs) -> float:
    """VMEM bytes per X column of one grid step. Each slab is ``(rows,
    dtype)`` or ``(rows, dtype, columns per X column)``; rows are padded
    to the dtype's sublane tile (8 for 32-bit, 32 for 8-bit), and every
    slab is counted twice for its two buffers."""
    total = 0.0
    for rows, dtype, *per in slabs:
        size = jnp.dtype(dtype).itemsize
        tile = 32 // size
        total += -(-rows // tile) * tile * size * (per[0] if per else 1)
    return 2 * total


def _budget_cols(col_bytes: float) -> int:
    """The widest 128-lane-aligned slab whose buffers fit the budget."""
    return max(128, int(_SLAB_VMEM_BYTES // col_bytes) // 128 * 128)


def _plan_blocks(x: int, x_block: int | None, interpret: bool,
                 col_bytes: float) -> int:
    """Block width for tiling the X axis.

    The X grid exists to bound VMEM residency on real TPUs, and every grid
    step costs a fixed overhead, so the compiled default is the widest slab
    whose buffers fit ``_SLAB_VMEM_BYTES`` at ``col_bytes`` (``_col_bytes``
    of the kernel's slab-wide operands): a narrow plane gets a wide slab.
    Interpret mode (CPU validation / CI) has no VMEM and pays ~100 µs of
    interpreter overhead PER GRID STEP, so its default is one whole-X
    block. An explicit ``x_block`` is always honored (the multi-block path
    is exercised in tests via small explicit blocks).

    A requested ``x_block`` is re-planned into ``ceil(X / x_block)``
    equal-width blocks instead of always tiling at the full width: the
    trailing block's waste drops from up to ``x_block - 1`` columns to
    under one lane tile. Blocks stay 128-lane aligned whenever the
    caller's ``x_block`` is (the Mosaic tiling constraint), and never grow
    past it; tiny or unaligned test sizes fall back to align=1.
    Non-dividing trailing blocks are handled by Pallas's edge masking — no
    host-side zero-pad / crop copies of the plane."""
    if x_block is None:
        x_block = x if interpret else _budget_cols(col_bytes)
    x_block = min(x_block, x)
    align = 128 if (x_block % 128 == 0 and x >= 128) else 1
    k = -(-x // x_block)          # number of grid steps
    per = -(-x // k)              # ceil(x / k) columns per step
    return -(-per // align) * align


def _plan_quant_blocks(xp: int, qblock: int, x_block: int | None,
                       interpret: bool, col_bytes: float) -> int:
    """Slab width for the kernels that read per-block scales: a multiple
    of ``qblock`` so each scale belongs to one slab, and — compiled — of
    ``128 * qblock`` so the (rows, x_block // qblock) scale block fills
    whole 128-lane tiles. The compiled default is the budget's width in
    whole such units (one unit at least), so the rounding up keeps it in
    the budget. A slab at least as wide as the plane becomes one
    whole-array block, which Mosaic accepts at any width."""
    unit = qblock if interpret else 128 * qblock
    if x_block is None and not interpret:
        x_block = max(unit, _budget_cols(col_bytes) // unit * unit)
    x_block = _plan_blocks(xp, x_block, interpret, col_bytes)
    return min(-(-x_block // unit) * unit, xp)


# Planes of at most one fp32 sublane tile of rows mix on the VPU.
_VPU_ROWS = 8


def _mix_rows_kernel(w_ref, c_ref, o_ref):
    """W·C for a plane of few rows: o_i = Σ_j W[i, j]·c_j as fp32
    multiply-adds on the VPU, the weights read as scalars from SMEM. On
    the MXU a K = N ≤ 8 contraction fills a sliver of the array, six bf16
    passes deep at HIGHEST; fp32 products are at least as precise."""
    n = c_ref.shape[0]
    rows = [c_ref[j:j + 1, :].astype(jnp.float32) for j in range(n)]
    for i in range(n):
        acc = w_ref[i, 0] * rows[0]
        for j in range(1, n):
            acc = acc + w_ref[i, j] * rows[j]
        o_ref[i:i + 1, :] = acc.astype(o_ref.dtype)


def gossip_mix_flat(
    w: jnp.ndarray,  # (N, N) row-stochastic mixing weights
    c: jnp.ndarray,  # (N, X) flattened per-client parameters
    *,
    x_block: int | None = None,  # default: VMEM budget compiled, whole-X interpret
    interpret: bool | None = None,
) -> jnp.ndarray:
    """C ← W·C over the (N, X) plane in one ``pallas_call``: an MXU
    matmul per slab, or for N ≤ ``_VPU_ROWS`` the VPU contraction
    (``_mix_rows_kernel``, W as a scalar-prefetch operand in SMEM)."""
    n, x = c.shape
    interpret = resolve_interpret(interpret)
    x_block = _plan_blocks(x, x_block, interpret,
                           _col_bytes((n, c.dtype), (n, c.dtype)))
    grid = (-(-x // x_block),)
    out_shape = jax.ShapeDtypeStruct((n, x), c.dtype)
    if n <= _VPU_ROWS:
        slab = pl.BlockSpec((n, x_block), lambda i, w: (0, i))
        return pl.pallas_call(
            _mix_rows_kernel,
            grid_spec=pltpu.PrefetchScalarGridSpec(
                num_scalar_prefetch=1, grid=grid, in_specs=[slab],
                out_specs=slab),
            out_shape=out_shape,
            interpret=interpret,
        )(w.astype(jnp.float32), c)
    return pl.pallas_call(
        _mix_kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((n, n), lambda i: (0, 0)),
            pl.BlockSpec((n, x_block), lambda i: (0, i)),
        ],
        out_specs=pl.BlockSpec((n, x_block), lambda i: (0, i)),
        out_shape=out_shape,
        interpret=interpret,
    )(w, c)


def _mix_stack_kernel(w_ref, c_ref, o_ref):
    w = w_ref[...].astype(jnp.float32)       # (N, N)
    c = c_ref[...][0].astype(jnp.float32)    # (1, N, x_block) -> (N, x_block)
    o_ref[...] = _mxu(w, c).astype(o_ref.dtype)[None]


def gossip_mix_stack(
    w: jnp.ndarray,  # (N, N) mixing weights, shared by every stack
    c: jnp.ndarray,  # (S, N, X) packed center stacks
    *,
    x_block: int | None = None,  # default: VMEM budget compiled, whole-X interpret
    interpret: bool | None = None,
) -> jnp.ndarray:
    """Mix EVERY cluster stack of a packed (S, N, X) plane with the same
    weight matrix in ONE ``pallas_call``: grid = (S, x_blocks), each step
    one (N×N)·(N×x_block) MXU matmul on cluster s's slab. This is the
    FedEM/FedSoft-shaped exchange (all S models move every round) — the
    pytree layout pays S × n_leaves kernel launches for the same traffic."""
    s, n, x = c.shape
    interpret = resolve_interpret(interpret)
    x_block = _plan_blocks(x, x_block, interpret,
                           _col_bytes((n, c.dtype), (n, c.dtype)))
    return pl.pallas_call(
        _mix_stack_kernel,
        grid=(s, -(-x // x_block)),
        in_specs=[
            pl.BlockSpec((n, n), lambda si, i: (0, 0)),
            pl.BlockSpec((1, n, x_block), lambda si, i: (si, 0, i)),
        ],
        out_specs=pl.BlockSpec((1, n, x_block), lambda si, i: (si, 0, i)),
        out_shape=jax.ShapeDtypeStruct((s, n, x), c.dtype),
        interpret=interpret,
    )(w, c)


def gossip_mix_tree(w: jnp.ndarray, c_tree, *, x_block: int | None = None,
                    interpret: bool | None = None):
    """Apply the mix to a pytree of (N, ...) leaves (flatten / unflatten).

    One ``pallas_call`` PER LEAF with ragged sub-block tails — kept as the
    compatibility path for pytree states. The packed parameter plane
    (core/packing.py) feeds ``gossip_mix_flat`` directly: exactly one call
    over the whole (N, X) buffer per round.
    """
    def one(leaf):
        n = leaf.shape[0]
        flat = leaf.reshape(n, -1)
        mixed = gossip_mix_flat(w, flat, x_block=x_block, interpret=interpret)
        return mixed.reshape(leaf.shape).astype(leaf.dtype)

    return jax.tree.map(one, c_tree)


def _slab_activity(col_active: jnp.ndarray, k: int,
                   x_block: int) -> jnp.ndarray:
    """(k,) int32 per-slab activity bits from (X,) column activity: 1 iff
    any column of the slab is live. Scalar-prefetched into SMEM."""
    act = jnp.pad(col_active.astype(jnp.float32),
                  (0, k * x_block - col_active.shape[0]))
    return (jnp.sum(act.reshape(k, x_block), axis=1) > 0).astype(jnp.int32)


def _mix_sparse_kernel(a_ref, w_ref, c_ref, o_ref):
    """W·C on one slab, predicated on the slab's activity bit: a slab
    whose every column is dead for every client skips the MXU matmul and
    writes zeros — the exact masked-mix result for an all-dead slab."""
    live = a_ref[pl.program_id(0)] > 0

    @pl.when(live)
    def _mix():
        w = w_ref[...].astype(jnp.float32)       # (N, N)
        c = c_ref[...].astype(jnp.float32)       # (N, x_block)
        o_ref[...] = _mxu(w, c).astype(o_ref.dtype)

    @pl.when(jnp.logical_not(live))
    def _dead():
        o_ref[...] = jnp.zeros_like(o_ref[...])


def gossip_mix_sparse(
    w: jnp.ndarray,           # (N, N) mixing weights
    c: jnp.ndarray,           # (N, X) plane slab, ZERO on dead columns
    col_active: jnp.ndarray,  # (X,) float {0,1}: any client keeps column
    *,
    x_block: int | None = None,  # default: VMEM budget compiled, whole-X interpret
    interpret: bool | None = None,
) -> jnp.ndarray:
    """Mask-aware W·C for the sparse (DisPFL) exchange: the grid still
    tiles the full X axis (shapes stay static), but each slab carries a
    traced one-element activity flag — computed here as "any active column
    in the slab" from the column-activity vector — and ``pl.when``
    predication skips the matmul for all-dead 128-aligned slabs, writing
    exact zeros instead. The activity bits are a scalar-prefetch operand
    (SMEM, one int32 per slab). Callers must pass ``c`` already projected
    onto the active support (masked values or the mask itself), which is
    what makes the skip exact rather than approximate."""
    n, x = c.shape
    if col_active.shape != (x,):
        raise ValueError(
            f"column activity {col_active.shape} does not match plane "
            f"width {x}"
        )
    interpret = resolve_interpret(interpret)
    x_block = _plan_blocks(x, x_block, interpret,
                           _col_bytes((n, c.dtype), (n, c.dtype)))
    k = -(-x // x_block)
    return pl.pallas_call(
        _mix_sparse_kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(k,),
            in_specs=[
                pl.BlockSpec((n, n), lambda i, a: (0, 0)),
                pl.BlockSpec((n, x_block), lambda i, a: (0, i)),
            ],
            out_specs=pl.BlockSpec((n, x_block), lambda i, a: (0, i)),
        ),
        out_shape=jax.ShapeDtypeStruct((n, x), c.dtype),
        interpret=interpret,
    )(_slab_activity(col_active, k, x_block), w, c)


def _mix_dequant_kernel(w_ref, q_ref, sc_ref, o_ref, *, qblock: int):
    """Fused dequantize + mix on one (N, x_block) slab of the QUANTIZED
    plane: o = W · (q ⊙ repeat(scale, qblock)). The mix reads int8 values
    (plus one fp32 scale per ``qblock`` columns) from HBM — ~4× less read
    traffic than mixing a materialized fp32 decode."""
    w = w_ref[...].astype(jnp.float32)        # (M, N)
    q = q_ref[...].astype(jnp.float32)        # (N, x_block) int8 payload
    sc = sc_ref[...].astype(jnp.float32)      # (N, x_block // qblock)
    c = q * jnp.repeat(sc, qblock, axis=1)
    o_ref[...] = _mxu(w, c).astype(o_ref.dtype)


def gossip_mix_dequant(
    w: jnp.ndarray,       # (M, N) mixing weights (M == N for gossip;
                          # M == B request rows for mixture serving)
    q: jnp.ndarray,       # (N, Xp) int8 quantized plane (comm/codecs)
    scales: jnp.ndarray,  # (N, Xp // qblock) fp32 per-block scales
    *,
    qblock: int,                 # quantization block width along X
    x_block: int | None = None,  # default: VMEM budget compiled, whole-X interpret
    interpret: bool | None = None,
) -> jnp.ndarray:
    """Compressed exchange in ONE ``pallas_call``: dequantize the int8
    payload (per-block scales) and apply Eq. (1)'s W·C on each slab without
    ever materializing the fp32 decode in HBM.

    The weight matrix may be rectangular: gossip passes the square (N, N)
    round-mixing matrix; the serving layer (serve/server.py) passes a
    (B, S) batch of per-request mixture weights over the S-row cluster
    plane — Eq. (2) as the same fused kernel.

    ``q`` comes padded to a whole number of scale blocks
    (comm/codecs.quant_encode pads the tail with exact-zero quanta), so the
    grid tiles an X axis that is a multiple of ``qblock`` and the slab's
    scale columns align exactly — the caller crops the fp32 result back to
    the logical width X. Slab widths are planned like the other kernels
    here (equal-width, 128-lane aligned) then rounded up by
    ``_plan_quant_blocks`` so every scale belongs to exactly one slab."""
    n, xp = q.shape
    m = w.shape[0]
    if w.shape[1] != n:
        raise ValueError(f"weights {w.shape} do not match plane rows {n}")
    if xp % qblock != 0 or scales.shape != (n, xp // qblock):
        raise ValueError(
            f"quantized plane {q.shape} / scales {scales.shape} do not "
            f"tile with qblock={qblock}"
        )
    interpret = resolve_interpret(interpret)
    x_block = _plan_quant_blocks(
        xp, qblock, x_block, interpret,
        _col_bytes((n, q.dtype), (n, scales.dtype, 1 / qblock),
                   (m, jnp.float32)))
    return pl.pallas_call(
        functools.partial(_mix_dequant_kernel, qblock=qblock),
        grid=(-(-xp // x_block),),
        in_specs=[
            pl.BlockSpec((m, n), lambda i: (0, 0)),
            pl.BlockSpec((n, x_block), lambda i: (0, i)),
            pl.BlockSpec((n, x_block // qblock), lambda i: (0, i)),
        ],
        out_specs=pl.BlockSpec((m, x_block), lambda i: (0, i)),
        out_shape=jax.ShapeDtypeStruct((m, xp), jnp.float32),
        interpret=interpret,
    )(w, q, scales)


def _mix_dequant_masked_kernel(a_ref, w_ref, q_ref, sc_ref, m_ref, o_ref,
                               *, qblock: int):
    """Fused dequantize + sender-mask + mix on one slab, predicated on the
    slab activity bit: o = W · (q ⊙ repeat(scale) ⊙ M). All-dead slabs
    write exact zeros without touching the payload."""
    live = a_ref[pl.program_id(0)] > 0

    @pl.when(live)
    def _mix():
        w = w_ref[...].astype(jnp.float32)        # (M, N)
        q = q_ref[...].astype(jnp.float32)        # (N, x_block)
        sc = sc_ref[...].astype(jnp.float32)      # (N, x_block // qblock)
        m = m_ref[...].astype(jnp.float32)        # (N, x_block)
        c = q * jnp.repeat(sc, qblock, axis=1) * m
        o_ref[...] = _mxu(w, c).astype(o_ref.dtype)

    @pl.when(jnp.logical_not(live))
    def _dead():
        o_ref[...] = jnp.zeros_like(o_ref[...])


def gossip_mix_dequant_masked(
    w: jnp.ndarray,       # (M, N) mixing weights
    q: jnp.ndarray,       # (N, Xp) int8 quantized plane (comm/codecs)
    scales: jnp.ndarray,  # (N, Xp // qblock) fp32 per-block scales
    mask: jnp.ndarray,    # (N, X) float {0,1} per-sender masks, X <= Xp
    *,
    qblock: int,                 # quantization block width along X
    x_block: int | None = None,  # default: VMEM budget compiled, whole-X interpret
    interpret: bool | None = None,
) -> jnp.ndarray:
    """Masked variant of ``gossip_mix_dequant`` for the sparse exchange's
    numerator W·(M⊙Ĉ): the sender masks are applied IN the fused
    dequantize+mix pass (the fp32 decode still never exists in HBM), and
    slabs that are all-dead across every sender are skipped via the same
    traced activity bits as ``gossip_mix_sparse``. The mask is zero-padded
    to the quantized width; the caller crops the fp32 result to X."""
    n, xp = q.shape
    m_rows = w.shape[0]
    if w.shape[1] != n:
        raise ValueError(f"weights {w.shape} do not match plane rows {n}")
    if xp % qblock != 0 or scales.shape != (n, xp // qblock):
        raise ValueError(
            f"quantized plane {q.shape} / scales {scales.shape} do not "
            f"tile with qblock={qblock}"
        )
    if mask.ndim != 2 or mask.shape[0] != n or mask.shape[1] > xp:
        raise ValueError(
            f"mask {mask.shape} does not match quantized plane {q.shape}"
        )
    mask = jnp.pad(mask.astype(jnp.float32),
                   ((0, 0), (0, xp - mask.shape[1])))
    interpret = resolve_interpret(interpret)
    x_block = _plan_quant_blocks(
        xp, qblock, x_block, interpret,
        _col_bytes((n, q.dtype), (n, scales.dtype, 1 / qblock),
                   (n, mask.dtype), (m_rows, jnp.float32)))
    k = -(-xp // x_block)
    slab = pl.BlockSpec((n, x_block), lambda i, a: (0, i))
    return pl.pallas_call(
        functools.partial(_mix_dequant_masked_kernel, qblock=qblock),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(k,),
            in_specs=[
                pl.BlockSpec((m_rows, n), lambda i, a: (0, 0)),
                slab,
                pl.BlockSpec((n, x_block // qblock), lambda i, a: (0, i)),
                slab,
            ],
            out_specs=pl.BlockSpec((m_rows, x_block), lambda i, a: (0, i)),
        ),
        out_shape=jax.ShapeDtypeStruct((m_rows, xp), jnp.float32),
        interpret=interpret,
    )(_slab_activity(jnp.sum(mask, axis=0) > 0, k, x_block), w, q, scales,
      mask)


def int4_slab_width(qblock: int) -> int:
    """Columns per slab of the int4 serve kernel: 128 scale blocks, so the
    scale block is lane-aligned and each half-slab is a multiple of 128
    lanes (``qblock`` is even)."""
    return 128 * qblock


def int4_slab_layout(packed, scales, *, qblock: int):
    """Re-lay a paired-nibble int4 plane for ``mixture_mix_dequant4``.

    The artifact (comm/codecs.int4_pack) stores columns 2j and 2j+1 in
    the low and high nibble of byte j; unpacking that inside the kernel
    needs a lane interleave Mosaic cannot lower. Here, per slab of
    ``int4_slab_width(qblock)`` columns, byte j holds column j (low
    nibble) and column j + width/2 (high nibble), so the kernel writes two
    lane-aligned halves. The plane is zero-padded to whole slabs (code 0
    dequantizes to 0) and the scales likewise.

    A one-time load step in host numpy (like ``int4_unpack``, whose
    interleave would tile to 64x its size on a TPU). serve/server.py runs
    it once per resident plane; returns host arrays
    ``(slab_packed (S, k·width/2) uint8, scales (S, k·width/qblock))``."""
    from repro.comm.codecs import int4_unpack

    packed = np.asarray(packed, np.uint8)
    scales = np.asarray(scales, np.float32)
    s, xh = packed.shape
    xp = 2 * xh
    if qblock % 2 or xp % qblock or scales.shape != (s, xp // qblock):
        raise ValueError(
            f"packed plane {packed.shape} / scales {scales.shape} do not "
            f"tile with an even qblock={qblock}"
        )
    width = int4_slab_width(qblock)
    k = -(-xp // width)
    codes = np.zeros((s, k * width), np.uint8)    # 4-bit two's complement
    codes[:, :xp] = int4_unpack(packed, xp).view(np.uint8) & 0xF
    codes = codes.reshape(s, k, 2, width // 2)
    slabs = (codes[:, :, 0] | (codes[:, :, 1] << 4)).reshape(s, -1)
    scales = np.pad(scales, ((0, 0), (0, k * width // qblock - xp // qblock)))
    return slabs, scales


def _mixture_dequant4_kernel(u_ref, p_ref, sc_ref, o_ref, *, qblock: int):
    """Fused nibble-unpack + dequantize + mixture matmul on one slab of
    the slab-split int4 plane (``int4_slab_layout``): low nibbles are the
    slab's first half of columns, high nibbles its second half, so
    o = U · (unpack4(p) ⊙ scales) is two lane-aligned matmuls. The plane
    stays at ~0.5 byte/param in HBM — the serve path's hot format — and
    the fp32 cluster models never exist outside registers."""
    u = u_ref[...].astype(jnp.float32)        # (B, S)
    p = p_ref[...].astype(jnp.int32)          # (S, x_block // 2)
    half = p.shape[1]
    sc = jnp.repeat(sc_ref[...].astype(jnp.float32), qblock, axis=1)
    for h, nib in enumerate((p & 0xF, (p >> 4) & 0xF)):
        q = (nib - 16 * (nib > 7).astype(jnp.int32)).astype(jnp.float32)
        c = q * sc[:, h * half:(h + 1) * half]
        o_ref[:, h * half:(h + 1) * half] = _mxu(u, c).astype(o_ref.dtype)


def mixture_mix_dequant4(
    u: jnp.ndarray,       # (B, S) per-request mixture weights (Eq. (2))
    packed: jnp.ndarray,  # (S, k·width/2) uint8, int4_slab_layout
    scales: jnp.ndarray,  # (S, k·width/qblock) fp32, int4_slab_layout
    *,
    qblock: int,                 # quantization block width along X (even)
    interpret: bool | None = None,
) -> jnp.ndarray:
    """Personalized-parameter materialization for a request batch in ONE
    ``pallas_call`` over the int4 BIT-PACKED cluster plane: each grid step
    unpacks a (S, width) slab, dequantizes with the per-block scales, and
    contracts with the (B, S) mixture weights — Eq. (2) fused with the
    int4 decode, reading half a byte per parameter from HBM. Companion of
    ``gossip_mix_dequant`` (which reads the int8-storage payload). The
    plane comes re-laid by ``int4_slab_layout``; the caller crops the
    (B, k·width) result back to the logical width X."""
    s, xh = packed.shape
    width = int4_slab_width(qblock)
    xk = 2 * xh
    if qblock % 2 or xk % width or scales.shape != (s, xk // qblock):
        raise ValueError(
            f"packed plane {packed.shape} / scales {scales.shape} are not "
            f"in int4_slab_layout for qblock={qblock}"
        )
    b = u.shape[0]
    if u.shape != (b, s):
        raise ValueError(f"mixture weights {u.shape} != (B, {s})")
    return pl.pallas_call(
        functools.partial(_mixture_dequant4_kernel, qblock=qblock),
        grid=(xk // width,),
        in_specs=[
            pl.BlockSpec((b, s), lambda i: (0, 0)),
            pl.BlockSpec((s, width // 2), lambda i: (0, i)),
            pl.BlockSpec((s, width // qblock), lambda i: (0, i)),
        ],
        out_specs=pl.BlockSpec((b, width), lambda i: (0, i)),
        out_shape=jax.ShapeDtypeStruct((b, xk), jnp.float32),
        interpret=resolve_interpret(interpret),
    )(u, packed, scales)


def gossip_mix_encoded(w: jnp.ndarray, enc: dict, *, qblock: int,
                       x_out: int, out_dtype, interpret: bool | None = None):
    """The fused compressed exchange both comm call sites share
    (core/gossip's FedSPD mix and baselines/common's W-average): one
    ``gossip_mix_dequant`` pass over the encoded payload
    (``{"q", "scale"}`` from comm/codecs.quant_encode), cropped back to
    the logical width and cast to the plane dtype."""
    mixed = gossip_mix_dequant(w, enc["q"], enc["scale"], qblock=qblock,
                               interpret=interpret)
    return mixed[..., :x_out].astype(out_dtype)


def gossip_mix_encoded_masked(w: jnp.ndarray, enc: dict, mask: jnp.ndarray,
                              *, qblock: int, x_out: int, out_dtype,
                              interpret: bool | None = None):
    """Sparse-exchange companion of ``gossip_mix_encoded``: the numerator
    W·(M⊙Ĉ) of the support-renormalized mix as one masked
    dequantize+mix pass over the encoded payload."""
    mixed = gossip_mix_dequant_masked(w, enc["q"], enc["scale"], mask,
                                      qblock=qblock, interpret=interpret)
    return mixed[..., :x_out].astype(out_dtype)


def _mix_dp_kernel(w_ref, co_ref, cn_ref, sc_ref, *refs, sigma: float):
    """Fused DP sanitize + mix on one (N, x_block) slab:
    o = W · (c_old + scale ⊙ (c_new − c_old) + σ·noise).
    ``refs`` is (nz_ref, o_ref) when σ > 0, else just (o_ref,) — clip-only
    rounds carry no noise operand at all (no wasted HBM traffic)."""
    o_ref = refs[-1]
    w = w_ref[...].astype(jnp.float32)        # (N, N)
    co = co_ref[...].astype(jnp.float32)      # (N, x_block)
    cn = cn_ref[...].astype(jnp.float32)
    sc = sc_ref[...].astype(jnp.float32)      # (N, 1) per-client clip scale
    c = co + sc * (cn - co)
    if sigma > 0.0:
        c = c + sigma * refs[0][...].astype(jnp.float32)
    o_ref[...] = _mxu(w, c).astype(o_ref.dtype)


def gossip_mix_fused_dp(
    w: jnp.ndarray,      # (N, N) row-stochastic mixing weights
    c_old: jnp.ndarray,  # (N, X) pre-round selected centers (packed plane)
    c_new: jnp.ndarray,  # (N, X) post-local-update centers
    scale: jnp.ndarray,  # (N, 1) per-client L2 clip scale (precomputed)
    noise,               # (N, X) standard Gaussian draw; None iff sigma == 0
    sigma: float,        # dp_clip * dp_noise_multiplier (static)
    *,
    x_block: int | None = None,  # default: VMEM budget compiled, whole-X interpret
    interpret: bool | None = None,
) -> jnp.ndarray:
    """DP round in a single streaming pass: clip·scale + noise + W·C fused
    into one ``pallas_call`` over the packed plane, so the parameters are
    read from and written to HBM exactly once. The per-client clip scale
    (one flat L2 norm) and the noise array are tiny / cheap by comparison
    and are produced outside the kernel. Clip-only DP (sigma == 0) passes
    ``noise=None`` and the kernel takes no noise operand."""
    n, x = c_old.shape
    sigma = float(sigma)
    assert c_new.shape == (n, x)
    assert (noise is None) == (sigma <= 0.0)
    scale = scale.reshape(n, 1)
    planes = [c_old, c_new]
    if sigma > 0.0:
        assert noise.shape == (n, x)
        planes.append(noise)
    interpret = resolve_interpret(interpret)
    x_block = _plan_blocks(
        x, x_block, interpret,
        _col_bytes((n, c_old.dtype), *[(n, p.dtype) for p in planes]))
    slab = pl.BlockSpec((n, x_block), lambda i: (0, i))
    in_specs = [
        pl.BlockSpec((n, n), lambda i: (0, 0)),
        slab,
        slab,
        pl.BlockSpec((n, 1), lambda i: (0, 0)),
    ] + [slab] * (len(planes) - 2)
    operands = [w, c_old, c_new, scale] + planes[2:]
    return pl.pallas_call(
        functools.partial(_mix_dp_kernel, sigma=sigma),
        grid=(-(-x // x_block),),
        in_specs=in_specs,
        out_specs=slab,
        out_shape=jax.ShapeDtypeStruct((n, x), c_old.dtype),
        interpret=interpret,
    )(*operands)
