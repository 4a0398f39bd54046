"""Attention: reference, blocked (flash-style, pure JAX), SWA, decode.

Three execution tiers:

- ``ref_attention``   — O(L²) materialized scores. Test oracle; small shapes.
- ``blocked_attention`` — the flash algorithm (online softmax over KV blocks)
  written with a lax.scan over the *static* list of (q-block, kv-block)
  pairs. Causality and sliding windows prune the pair list at trace time, so
  compiled FLOPs match the true masked cost (≈½ of naive for causal, ∝W for
  windowed) and peak memory is O(block²) — this is what the 32k-prefill
  dry-runs lower. It is also structurally identical to the Pallas
  ``flash_attention`` kernel (kernels/flash_attention.py), which replaces it
  on real TPU hardware.
- ``decode_attention`` — one query token vs a (possibly sequence-sharded)
  KV cache; exposes (m, l, o) partials so the launch layer can combine
  shards with a stable-softmax psum (flash-decoding on ICI).

All functions take GQA-layout tensors:
  q: (B, Lq, Hq, hd)    k, v: (B, Lkv, Hkv, hd)   with Hq = G * Hkv.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

NEG_INF = -1e30


def _split_gqa(q: jnp.ndarray, n_kv: int) -> jnp.ndarray:
    """(B, L, Hq, hd) -> (B, L, Hkv, G, hd)."""
    b, l, hq, hd = q.shape
    return q.reshape(b, l, n_kv, hq // n_kv, hd)


@jax.named_scope("attn")
def ref_attention(
    q: jnp.ndarray,
    k: jnp.ndarray,
    v: jnp.ndarray,
    *,
    causal: bool = True,
    window: int | None = None,
    q_offset: int = 0,
) -> jnp.ndarray:
    """Materialized attention. Oracle for blocked/Pallas paths."""
    b, lq, hq, hd = q.shape
    n_kv = k.shape[2]
    qg = _split_gqa(q, n_kv).astype(jnp.float32)
    k32, v32 = k.astype(jnp.float32), v.astype(jnp.float32)
    scores = jnp.einsum("blkgd,bmkd->bkglm", qg, k32) / np.sqrt(hd)
    pos_q = jnp.arange(lq) + q_offset
    pos_k = jnp.arange(k.shape[1])
    mask = jnp.ones((lq, k.shape[1]), dtype=bool)
    if causal:
        mask &= pos_q[:, None] >= pos_k[None, :]
    if window is not None:
        mask &= pos_q[:, None] - pos_k[None, :] < window
    scores = jnp.where(mask[None, None, None], scores, NEG_INF)
    probs = jax.nn.softmax(scores, axis=-1)
    out = jnp.einsum("bkglm,bmkd->blkgd", probs, v32)
    return out.reshape(b, lq, hq, hd).astype(q.dtype)


def _block_pairs(n_q: int, n_kv: int, causal: bool, window: int | None,
                 q_block: int, kv_block: int):
    """Static (qi, ki) pair list. Causality/window prune at trace time.
    Bounds are computed in *positions* so unequal q/kv block sizes are
    handled exactly."""
    pairs = []
    for qi in range(n_q):
        q_lo = qi * q_block
        q_hi = q_lo + q_block - 1
        lo, hi = 0, n_kv - 1
        if causal:
            hi = min(hi, q_hi // kv_block)
        if window is not None:
            lo = max(lo, (q_lo - window + 1) // kv_block)
        for ki in range(lo, hi + 1):
            pairs.append((qi, ki))
    return np.array(pairs, dtype=np.int32)


def _fit_block(length: int, block: int) -> int:
    """Largest divisor of ``length`` that is <= ``block`` (lengths like
    whisper's 1500 encoder frames are not powers of two)."""
    block = min(block, length)
    while length % block:
        block -= 1
    return block


@jax.named_scope("attn")
def blocked_attention(
    q: jnp.ndarray,
    k: jnp.ndarray,
    v: jnp.ndarray,
    *,
    causal: bool = True,
    window: int | None = None,
    dyn_window: jnp.ndarray | None = None,
    q_block: int = 512,
    kv_block: int = 512,
    unroll: int | bool = 1,
) -> jnp.ndarray:
    """Flash-style attention via scan over static block pairs.

    ``window`` is a static sliding-window bound used to prune block pairs;
    ``dyn_window`` is an optional *traced* per-call window (used by
    local:global stacks where the window varies per layer inside a scan) —
    it can only tighten the mask, never widen past ``window``.
    """
    b, lq, hq, hd = q.shape
    lkv = k.shape[1]
    n_kvh = k.shape[2]
    g = hq // n_kvh
    scale = 1.0 / np.sqrt(hd)

    q_block = _fit_block(lq, q_block)
    kv_block = _fit_block(lkv, kv_block)
    n_q, n_k = lq // q_block, lkv // kv_block

    pairs = _block_pairs(n_q, n_k, causal, window, q_block, kv_block)

    qg = _split_gqa(q, n_kvh)  # (B, L, Hkv, G, hd)
    # accumulators in fp32
    acc = jnp.zeros((n_q, b, n_kvh, g, q_block, hd), jnp.float32)
    m = jnp.full((n_q, b, n_kvh, g, q_block), NEG_INF, jnp.float32)
    l = jnp.zeros((n_q, b, n_kvh, g, q_block), jnp.float32)

    pos_in_q = jnp.arange(q_block)
    pos_in_k = jnp.arange(kv_block)

    def body(carry, pair):
        acc, m, l = carry
        qi, ki = pair[0], pair[1]
        qb = jax.lax.dynamic_slice_in_dim(qg, qi * q_block, q_block, axis=1)
        kb = jax.lax.dynamic_slice_in_dim(k, ki * kv_block, kv_block, axis=1)
        vb = jax.lax.dynamic_slice_in_dim(v, ki * kv_block, kv_block, axis=1)
        s = (
            jnp.einsum(
                "blkgd,bmkd->bkglm",
                qb.astype(jnp.float32),
                kb.astype(jnp.float32),
            )
            * scale
        )  # (B, Hkv, G, q_block, kv_block)
        pq = qi * q_block + pos_in_q
        pk = ki * kv_block + pos_in_k
        mask = jnp.ones((q_block, kv_block), bool)
        if causal:
            mask &= pq[:, None] >= pk[None, :]
        if window is not None:
            mask &= pq[:, None] - pk[None, :] < window
        if dyn_window is not None:
            mask &= pq[:, None] - pk[None, :] < dyn_window
        s = jnp.where(mask[None, None, None], s, NEG_INF)

        m_prev = jax.lax.dynamic_index_in_dim(m, qi, 0, keepdims=False)
        l_prev = jax.lax.dynamic_index_in_dim(l, qi, 0, keepdims=False)
        a_prev = jax.lax.dynamic_index_in_dim(acc, qi, 0, keepdims=False)

        m_blk = jnp.max(s, axis=-1)
        m_new = jnp.maximum(m_prev, m_blk)
        p = jnp.exp(s - m_new[..., None])
        alpha = jnp.exp(m_prev - m_new)
        l_new = l_prev * alpha + jnp.sum(p, axis=-1)
        a_new = a_prev * alpha[..., None] + jnp.einsum(
            "bkglm,bmkd->bkgld", p, vb.astype(jnp.float32)
        )
        acc = jax.lax.dynamic_update_index_in_dim(acc, a_new, qi, 0)
        m = jax.lax.dynamic_update_index_in_dim(m, m_new, qi, 0)
        l = jax.lax.dynamic_update_index_in_dim(l, l_new, qi, 0)
        return (acc, m, l), None

    (acc, m, l), _ = jax.lax.scan(body, (acc, m, l), pairs, unroll=unroll)
    # (n_q, B, Hkv, G, q_block, hd) -> (B, L, Hq, hd)
    out = acc / jnp.maximum(l[..., None], 1e-30)
    out = jnp.moveaxis(out, 0, 3)  # (B, Hkv, G, n_q, q_block, hd)
    out = out.reshape(b, n_kvh, g, lq, hd)
    out = jnp.moveaxis(out, 3, 1).reshape(b, lq, hq, hd)
    return out.astype(q.dtype)


def attention(
    q, k, v, *, mode: str = "blocked", causal: bool = True,
    window: int | None = None, dyn_window=None,
    q_block: int = 512, kv_block: int = 512, unroll: int | bool = 1,
):
    if mode == "ref":
        out = ref_attention(q, k, v, causal=causal, window=window)
        if dyn_window is not None:
            # ref path with traced window: recompute mask via blocked path
            out = blocked_attention(
                q, k, v, causal=causal, window=window, dyn_window=dyn_window,
                q_block=q_block, kv_block=kv_block,
            )
        return out
    if mode == "blocked":
        return blocked_attention(
            q, k, v, causal=causal, window=window, dyn_window=dyn_window,
            q_block=q_block, kv_block=kv_block, unroll=unroll,
        )
    if mode == "pallas":
        from repro.kernels import ops as kops

        assert dyn_window is None, "pallas path requires static windows"
        return kops.flash_attention(q, k, v, causal=causal, window=window)
    raise ValueError(f"unknown attention mode {mode!r}")


# --------------------------------------------------------------------------
# Decode (single new token against a KV cache)
# --------------------------------------------------------------------------


def decode_attention_parts(
    q: jnp.ndarray,  # (B, 1, Hq, hd)
    k_cache: jnp.ndarray,  # (B, Lc, Hkv, hd) — possibly a shard
    v_cache: jnp.ndarray,
    positions: jnp.ndarray,  # (Lc,) global positions of the cache shard
    cur_pos: jnp.ndarray,  # () global position of the new token
    window: int | None = None,
    dyn_window: jnp.ndarray | None = None,
):
    """Stable-softmax partials (m, l, o) over this cache shard.

    Combine across shards with: M=max m; l'=Σ l·e^{m-M}; o'=Σ o·e^{m-M}.
    """
    b, _, hq, hd = q.shape
    n_kv = k_cache.shape[2]
    # _split_gqa gives (B, 1, Hkv, G, hd); drop the length-1 query axis
    qg = _split_gqa(q, n_kv)[:, 0].astype(jnp.float32)  # (B, Hkv, G, hd)
    s = jnp.einsum(
        "bkgd,bmkd->bkgm", qg, k_cache.astype(jnp.float32)
    ) / np.sqrt(hd)  # (B, Hkv, G, Lc)
    valid = positions[None, None, None, :] <= cur_pos
    if window is not None:
        valid &= cur_pos - positions[None, None, None, :] < window
    if dyn_window is not None:
        valid &= cur_pos - positions[None, None, None, :] < dyn_window
    s = jnp.where(valid, s, NEG_INF)
    m = jnp.max(s, axis=-1)  # (B, Hkv, G)
    p = jnp.exp(s - m[..., None])
    p = jnp.where(valid, p, 0.0)
    l = jnp.sum(p, axis=-1)
    o = jnp.einsum("bkgm,bmkd->bkgd", p, v_cache.astype(jnp.float32))
    return m, l, o


def combine_decode_parts(m, l, o, axis_name=None):
    """Finish decode attention from (m, l, o); psum across ``axis_name``
    shards if given (flash-decoding combine)."""
    if axis_name is not None:
        M = jax.lax.pmax(m, axis_name)
        alpha = jnp.exp(m - M)
        l = jax.lax.psum(l * alpha, axis_name)
        o = jax.lax.psum(o * alpha[..., None], axis_name)
    out = o / jnp.maximum(l[..., None], 1e-30)
    b, n_kv, g, hd = out.shape
    return out.reshape(b, 1, n_kv * g, hd)


def decode_attention(
    q, k_cache, v_cache, cur_pos, *, window=None, dyn_window=None, axis_name=None
):
    lc = k_cache.shape[1]
    if axis_name is None:
        positions = jnp.arange(lc)
    else:
        idx = jax.lax.axis_index(axis_name)
        positions = idx * lc + jnp.arange(lc)
    m, l, o = decode_attention_parts(
        q, k_cache, v_cache, positions, cur_pos, window=window, dyn_window=dyn_window
    )
    return combine_decode_parts(m, l, o, axis_name=axis_name).astype(q.dtype)
