"""Compressed-communication codecs for the packed parameter plane.

FedSPD's second headline claim is that selective, cluster-wise exchange
"substantially reduces communication costs"; DisPFL-style systems push the
same lever further with sparse/quantized payloads. This module is the wire
layer for every method in the registry: a codec turns the (N, X) /
(S, N, X) plane slice a round is about to exchange into an encoded payload
(what actually crosses an edge), and back into the dequantized values the
receivers mix. Because PR 3 made the packed plane universal, one
implementation on flat slices serves all 13 method ids.

Codecs (``CommConfig.codec``):

- ``fp32``  passthrough — the uncompressed baseline. By construction this
  is a bit-exact no-op: ``make_channel`` returns ``None`` and every call
  site keeps its original, unmodified code path (asserted in tests).
- ``int8`` / ``int4``  stochastic uniform quantization with per-block
  scales: each ``block``-wide slice of the X axis is scaled by
  ``max|x| / qmax`` and rounded stochastically (``floor(y + u)``,
  u ~ U[0,1)), which makes the codec UNBIASED: E[decode(encode(x))] = x.
  Wire cost: int8 ships one byte per value + fp32 scales
  (``X + 4·ceil(X/block)``); int4 ships REAL paired nibbles in uint8 +
  fp16 scales (``ceil(X/2) + 2·ceil(X/block)``). The int4 device payload
  keeps int8 storage in [-7, 7] — compute reads it unpacked — but the
  serialized wire/disk image (``Channel.serialize_payload``) is the
  bit-packed form, and its byte length equals ``wire_model_bytes``
  EXACTLY. int4 scales are rounded through fp16 at encode time so the
  device decode and the wire decode are bit-identical.
- ``topk``  magnitude sparsification: the k largest-|x| entries of each
  (X,)-message survive as (value, index) pairs; 8k bytes per message.
  Top-k is BIASED — pair it with ``error_feedback=True`` so the dropped
  mass re-enters the stream next round instead of being lost.

Error feedback (Karimireddy et al. 2019): the channel carries a per-client
residual e; each round transmits encode(x + e) and keeps
e' = (x + e) − decode(encode(x + e)). The residual lives in the method's
round-loop state (an ``ef`` field on the state NamedTuples), so it rides
vmap/donation like every other state leaf.

All codecs operate on arrays whose LAST axis is the flat message width X
and are shape-polymorphic over leading batch prefixes — the same channel
encodes a (N, X) selected-center slab and FedEM's full (S, N, X) stack.
"""
from __future__ import annotations

import dataclasses
from typing import Any, NamedTuple, Optional

import jax
import jax.numpy as jnp
import numpy as np

PyTree = Any

CODECS = ("fp32", "int8", "int4", "topk")


@dataclasses.dataclass(frozen=True)
class CommConfig:
    """Per-run communication-compression knob (``run_method(comm=...)``).

    ``block`` is the quantization-scale granularity along X (one fp32
    scale per block). ``k`` is the survivors-per-message count for
    ``topk`` (default: X // 16). ``error_feedback`` threads the residual
    state through the round loop."""

    codec: str = "fp32"
    block: int = 256
    k: Optional[int] = None
    error_feedback: bool = False

    def __post_init__(self):
        if self.codec not in CODECS:
            raise ValueError(
                f"unknown codec {self.codec!r}; expected one of {CODECS}"
            )
        if self.block <= 0:
            raise ValueError(f"block must be positive, got {self.block}")
        if self.k is not None and self.k <= 0:
            raise ValueError(f"k must be positive, got {self.k}")


def available_codecs() -> tuple[str, ...]:
    return CODECS


# --------------------------------------------------------------------------
# Quantization: stochastic uniform with per-block scales
# --------------------------------------------------------------------------


def _quant_bits(codec: str) -> int:
    return {"int8": 8, "int4": 4}[codec]


def _pad_width(x_width: int, block: int) -> tuple[int, int]:
    nq = -(-x_width // block)
    return nq, nq * block


def quant_encode(x: jnp.ndarray, key: jax.Array, *, bits: int,
                 block: int, scale_dtype=jnp.float32,
                 rounding: str = "stochastic") -> dict:
    """x (..., X) -> {"q": (..., Xp) int8, "scale": (..., Xp/block) f32}.

    Xp pads X up to a whole number of scale blocks; the padded tail
    quantizes to exact zeros, so the fused dequantize+mix kernel can run
    on the padded width with no edge special-casing and the caller crops
    the output back to X.

    ``scale_dtype`` rounds the per-block scales through a narrower wire
    dtype (int4 ships fp16 scales) BEFORE the division, so quantizing and
    dequantizing with the stored scale keeps the one-step error bound and
    the device stream is bit-identical to what a receiver reconstructs
    from the serialized payload. ``rounding="nearest"`` (u = 1/2,
    ``key`` may be None) is the deterministic variant used when shipping
    a plane once — e.g. a servable artifact — where unbiasedness across
    repeated sends buys nothing and halving the worst-case error does."""
    x_width = x.shape[-1]
    nq, xp = _pad_width(x_width, block)
    qmax = float(2 ** (bits - 1) - 1)
    xb = jnp.pad(
        x.astype(jnp.float32), [(0, 0)] * (x.ndim - 1) + [(0, xp - x_width)]
    ).reshape(x.shape[:-1] + (nq, block))
    scale = jnp.max(jnp.abs(xb), axis=-1) / qmax          # (..., nq)
    if jnp.dtype(scale_dtype) != jnp.float32:
        scale = scale.astype(scale_dtype).astype(jnp.float32)
    y = xb / jnp.maximum(scale, 1e-12)[..., None]          # |y| <= qmax
    if rounding == "nearest":
        u = 0.5
    elif rounding == "stochastic":
        u = jax.random.uniform(key, xb.shape, jnp.float32)
    else:
        raise ValueError(f"unknown rounding {rounding!r}")
    q = jnp.clip(jnp.floor(y + u), -qmax, qmax).astype(jnp.int8)
    return {"q": q.reshape(x.shape[:-1] + (xp,)), "scale": scale}


def quant_decode(enc: dict, *, block: int, x_width: int) -> jnp.ndarray:
    q, scale = enc["q"], enc["scale"]
    xb = q.reshape(q.shape[:-1] + (scale.shape[-1], block))
    out = xb.astype(jnp.float32) * scale[..., None].astype(jnp.float32)
    return out.reshape(q.shape)[..., :x_width]


# --------------------------------------------------------------------------
# int4 bit packing: paired two's-complement nibbles in uint8
# --------------------------------------------------------------------------


def int4_pack(q: jnp.ndarray) -> jnp.ndarray:
    """(..., W) int8 values in [-8, 7] -> (..., ceil(W/2)) uint8.

    Adjacent pairs along the last axis share one byte: element 2i in the
    low nibble, 2i+1 in the high nibble, both as two's-complement 4-bit
    values. Odd widths pad one zero nibble (the wire format's
    ``ceil(X/2)``). Works identically as host numpy or traced jnp."""
    w = q.shape[-1]
    if w % 2:
        q = jnp.pad(q, [(0, 0)] * (q.ndim - 1) + [(0, 1)])
    lo = q[..., 0::2].astype(jnp.uint8) & 0xF
    hi = q[..., 1::2].astype(jnp.uint8) & 0xF
    return lo | (hi << 4)


def int4_unpack(packed, width: int) -> np.ndarray:
    """Inverse of ``int4_pack``: (..., ceil(W/2)) uint8 -> (..., W) int8.

    Bit-exact: ``int4_unpack(int4_pack(q), q.shape[-1]) == q`` for every
    int8 ``q`` in [-8, 7] (asserted in tests/test_comm.py). Host numpy,
    the artifact reader's side: on a TPU the interleave's (..., 2)-minor
    intermediate would tile to 64x its size."""
    packed = np.asarray(packed, np.uint8)
    v = np.empty(packed.shape[:-1] + (2 * packed.shape[-1],), np.int8)
    v[..., 0::2] = packed & 0xF
    v[..., 1::2] = packed >> 4
    return np.where(v > 7, v - 16, v).astype(np.int8)[..., :width]


# --------------------------------------------------------------------------
# Top-k magnitude sparsification
# --------------------------------------------------------------------------


def topk_encode(x: jnp.ndarray, k: int) -> dict:
    """x (..., X) -> {"v": (..., k) f32, "i": (..., k) int32}."""
    _, idx = jax.lax.top_k(jnp.abs(x.astype(jnp.float32)), k)
    vals = jnp.take_along_axis(x.astype(jnp.float32), idx, axis=-1)
    return {"v": vals, "i": idx.astype(jnp.int32)}


def topk_decode(enc: dict, *, x_width: int) -> jnp.ndarray:
    v, i = enc["v"], enc["i"]
    batch = v.shape[:-1]
    flat_v = v.reshape((-1, v.shape[-1]))
    flat_i = i.reshape((-1, i.shape[-1]))
    out = jax.vmap(
        lambda vv, ii: jnp.zeros((x_width,), jnp.float32).at[ii].set(vv)
    )(flat_v, flat_i)
    return out.reshape(batch + (x_width,))


# --------------------------------------------------------------------------
# Channel: a codec bound to a message width, plus error feedback
# --------------------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class Channel:
    """One codec bound to a flat message width X.

    ``fused`` marks codecs whose encoded payload (int8 values + per-block
    scales) the fused Pallas kernel (kernels/gossip_mix.gossip_mix_dequant)
    can consume directly — the mix then reads the COMPRESSED plane from
    HBM instead of a materialized fp32 decode. ``wire_model_bytes`` is the
    exact physical payload per single-model message; logical bytes (what
    the uncompressed exchange would have moved) stay with the original
    dtypes, so reported compression ratios are honest."""

    cfg: CommConfig
    x: int  # logical flat message width

    @property
    def has_ef(self) -> bool:
        return self.cfg.error_feedback

    @property
    def fused(self) -> bool:
        return self.cfg.codec in ("int8", "int4")

    @property
    def k(self) -> int:
        return self.cfg.k if self.cfg.k is not None else max(1, self.x // 16)

    @property
    def scale_wire_dtype(self):
        """Dtype the per-block scales ship in: fp16 for int4 (half the
        scale overhead of a codec whose whole point is halving bytes),
        fp32 for int8. Encode rounds through this dtype, so device and
        wire decodes agree bit for bit."""
        return np.float16 if self.cfg.codec == "int4" else np.float32

    @property
    def scale_bytes(self) -> int:
        """Per-message scale payload: one ``scale_wire_dtype`` scalar per
        quantization block."""
        nq, _ = _pad_width(self.x, self.cfg.block)
        return int(np.dtype(self.scale_wire_dtype).itemsize * nq)

    @property
    def wire_model_bytes(self) -> int:
        c = self.cfg
        if c.codec == "fp32":
            return 4 * self.x
        if c.codec == "int8":
            return int(self.x + self.scale_bytes)
        if c.codec == "int4":
            # paired nibbles: exactly what serialize_payload emits
            return int(-(-self.x // 2) + self.scale_bytes)
        return int(8 * min(self.k, self.x))  # topk: fp32 value + int32 index

    def wire_ratio(self, logical_model_bytes: int) -> float:
        """wire / logical bytes per message (exact, static per model)."""
        return self.wire_model_bytes / float(logical_model_bytes)

    # -------------------------------------------------- encode / decode

    @jax.named_scope("comm/encode")
    def encode(self, x: jnp.ndarray, key: jax.Array, *,
               rounding: str = "stochastic") -> dict:
        return self._encode(x, key, rounding=rounding)

    def _encode(self, x, key, *, rounding="stochastic") -> dict:
        c = self.cfg
        if c.codec in ("int8", "int4"):
            return quant_encode(x, key, bits=_quant_bits(c.codec),
                                block=c.block,
                                scale_dtype=self.scale_wire_dtype,
                                rounding=rounding)
        if c.codec == "topk":
            return topk_encode(x, min(self.k, self.x))
        raise ValueError(f"codec {c.codec!r} has no encoded form")

    def decode(self, enc: dict) -> jnp.ndarray:
        c = self.cfg
        if c.codec in ("int8", "int4"):
            return quant_decode(enc, block=c.block, x_width=self.x)
        return topk_decode(enc, x_width=self.x)

    # ------------------------------------------------- wire serialization

    def serialize_payload(self, enc: dict) -> bytes:
        """The exact physical wire/disk image of an encoded message batch:
        the quantized payload (int4: paired nibbles, int8: raw bytes)
        followed by the per-block scales in ``scale_wire_dtype``, both
        cropped to the LOGICAL width X (the encode-side pad is zeros the
        receiver reconstructs). ``len(...) == n_messages ×
        wire_model_bytes`` exactly — wire accounting is the serializer,
        not an estimate (asserted in tests/test_comm.py)."""
        c = self.cfg
        if c.codec not in ("int8", "int4"):
            raise ValueError(
                f"codec {c.codec!r} has no plane wire format (quantized "
                "codecs only)"
            )
        q = np.asarray(enc["q"])[..., : self.x]
        sc = np.ascontiguousarray(
            np.asarray(enc["scale"]), dtype=self.scale_wire_dtype
        )
        if c.codec == "int4":
            payload = np.asarray(int4_pack(jnp.asarray(q)))
        else:
            payload = q.astype(np.int8)
        return np.ascontiguousarray(payload).tobytes() + sc.tobytes()

    def deserialize_payload(self, data: bytes,
                            batch_prefix: tuple = ()) -> dict:
        """Inverse of ``serialize_payload`` for a ``batch_prefix``-shaped
        message batch: reconstructs the device-form encoding ({"q" int8
        padded to whole scale blocks, "scale" f32}) such that
        ``decode(deserialize(serialize(enc)))`` is bit-identical to
        ``decode(enc)``."""
        c = self.cfg
        nq, xp = _pad_width(self.x, c.block)
        batch = tuple(int(b) for b in batch_prefix)
        n_msgs = int(np.prod(batch)) if batch else 1
        if len(data) != n_msgs * self.wire_model_bytes:
            raise ValueError(
                f"payload is {len(data)} bytes; {batch} × "
                f"{self.cfg.codec} messages of width {self.x} need "
                f"{n_msgs * self.wire_model_bytes}"
            )
        qw = -(-self.x // 2) if c.codec == "int4" else self.x
        split = n_msgs * qw
        raw = np.frombuffer(data[:split], dtype=np.uint8).reshape(
            batch + (qw,))
        if c.codec == "int4":
            q = int4_unpack(raw, self.x)
        else:
            q = raw.view(np.int8)
        q = np.pad(q, [(0, 0)] * len(batch) + [(0, xp - self.x)])
        sc = np.frombuffer(data[split:], dtype=self.scale_wire_dtype)
        sc = sc.reshape(batch + (nq,)).astype(np.float32)
        return {"q": jnp.asarray(q), "scale": jnp.asarray(sc)}

    # ---------------------------------------------- round-loop interface

    def init_residual(self, batch_prefix: tuple) -> Optional[jnp.ndarray]:
        """Per-client error-feedback residual carried in the round loop —
        zeros of the full message shape, or None when EF is off (the state
        pytree then carries an empty subtree)."""
        if not self.has_ef:
            return None
        return jnp.zeros(tuple(batch_prefix) + (self.x,), jnp.float32)

    @jax.named_scope("comm/encode")
    def encode_stream(self, x: jnp.ndarray, key: jax.Array,
                      ef: Optional[jnp.ndarray], *, need_hat: bool = False):
        """One channel use: returns (enc, x_hat_or_None, ef').

        ``x_hat`` (the receiver-side decode) is materialized only when the
        residual update or the caller (``need_hat``) demands it — the
        fused Pallas path without EF never decodes outside the kernel."""
        msg = x.astype(jnp.float32) + ef if ef is not None else x
        enc = self._encode(msg, key)
        x_hat = None
        if self.has_ef or need_hat:
            x_hat = self.decode(enc)
        if self.has_ef:
            ef = msg.astype(jnp.float32) - x_hat
        return enc, x_hat, ef

    def roundtrip(self, x: jnp.ndarray, key: jax.Array,
                  ef: Optional[jnp.ndarray]):
        """decode(encode(x + ef)) plus the residual update: what the
        receivers see, and what the sender keeps. Returns (x_hat, ef')."""
        enc, x_hat, ef = self.encode_stream(x, key, ef, need_hat=True)
        return x_hat, ef


def sparse_wire_model_bytes(cfg: Optional[CommConfig], x: int,
                            k_active: int) -> int:
    """Exact physical bytes per single-model SPARSE message: nnz payload
    plus support bitmap (core/sparse masks; DisPFL).

    The sparse wire format gathers the ``k_active`` active values into a
    compact run, encodes THAT (mask-then-encode: quantization blocks tile
    the compact run, so scales cover nnz — never dead air), and prepends a
    ``ceil(X/8)``-byte support bitmap the receiver scatters by. All terms
    are static given (codec, X, density), so accounting stays a
    trace-free per-message constant like ``Channel.wire_model_bytes``:

    - fp32: ``4·k + ceil(X/8)``
    - int8: ``k + 4·ceil(k/block) + ceil(X/8)``
    - int4: ``ceil(k/2) + 2·ceil(k/block) + ceil(X/8)``
    - topk: ``8·min(topk_k, k)`` — NO bitmap: the top-k payload already
      carries explicit (value, index) pairs, and survivors can only come
      from the active support, so masking never inflates the message

    For the density-scaling codecs (fp32/int8/int4) the result is bounded
    by ``density·dense_wire + bitmap`` (asserted in tests/test_sparse.py);
    topk is instead bounded by its own dense cost.
    """
    bitmap = -(-x // 8)
    if cfg is None or cfg.codec == "fp32":
        return int(4 * k_active + bitmap)
    if cfg.codec == "int8":
        return int(k_active + 4 * -(-k_active // cfg.block) + bitmap)
    if cfg.codec == "int4":
        return int(-(-k_active // 2) + 2 * -(-k_active // cfg.block)
                   + bitmap)
    k_top = cfg.k if cfg.k is not None else max(1, x // 16)
    return int(8 * min(k_top, k_active))


def make_channel(cfg: Optional[CommConfig], x_width: int) -> Optional[Channel]:
    """Channel for a flat message width — or ``None`` for no compression.

    ``codec="fp32"`` maps to ``None`` BY DESIGN: the uncompressed exchange
    must be the exact original code path (bit-exact no-op, no extra key
    splits, no residual state), so wire accounting for it is handled by
    the driver without a channel object."""
    if cfg is None or cfg.codec == "fp32":
        return None
    return Channel(cfg=cfg, x=int(x_width))


class WithEF(NamedTuple):
    """State rider for methods whose round-loop state is a bare array
    (FedAvg's packed plane): the error-feedback residual travels next to
    the payload through vmap/jit/donation like any other state leaf.
    Methods with NamedTuple states grow an ``ef`` field instead."""

    x: Any
    ef: Any


def split_ef(state, channel: Optional[Channel]):
    """(payload, residual) from a possibly-WithEF-wrapped state."""
    if channel is not None and channel.has_ef:
        return state.x, state.ef
    return state, None


def join_ef(x, ef, channel: Optional[Channel]):
    """Inverse of ``split_ef`` — wrap only when the channel carries EF, so
    non-EF runs keep their state pytree (and jit cache keys) unchanged."""
    if channel is not None and channel.has_ef:
        return WithEF(x, ef)
    return x


def exchange(channel: Optional[Channel], x: jnp.ndarray, mix,
             key: Optional[jax.Array], ef: Optional[jnp.ndarray]):
    """The reference compressed exchange: mix(decode(encode(x + ef))).

    ``mix`` is any callable on the decoded plane slice (a baseline's W·C
    average, FedSPD's Eq. (1), FedSoft's importance-weighted aggregate).
    With ``channel=None`` this is exactly ``mix(x)`` — the fp32 no-op.
    Returns (mixed, ef')."""
    if channel is None:
        return mix(x), ef
    x_hat, ef = channel.roundtrip(x, key, ef)
    return mix(x_hat), ef
