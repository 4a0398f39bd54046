"""Whisper base (arXiv:2212.04356; openai/whisper-base) as this repository
runs it, with a plain float32 reference of the same model.

``whisper_base.json`` beside this file holds the sizes as run and every
departure from the published model. ``build`` makes the program's model
from those sizes; ``reference_loss`` is the per-example next-token loss
written out in plain ``jax.numpy`` from the description, importing nothing
of the program and reading its parameters by name; ``flops_per_sample``
counts one forward pass from the shapes.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp


def build(sizes: dict):
    """The program's (ArchConfig, ModelBundle) for these sizes, built as
    ``launch/train`` builds a full-size model."""
    from repro.configs.base import ArchConfig
    from repro.models.registry import build_model

    cfg = ArchConfig(**sizes["run"])
    return cfg, build_model(cfg, attn_mode="blocked")


def flops_per_sample(run: dict, seq: int) -> float:
    """Forward FLOPs of one example: 2 per multiply-add, attention scores
    and values counted over the full (unmasked) square, the LM head over
    the published vocabulary."""
    d, ff = run["d_model"], run["d_ff"]
    de = run.get("encoder_d_model") or d
    t, le, ld = run["encoder_frames"], run["encoder_layers"], run["n_layers"]
    enc = le * (8 * t * de * de + 4 * t * t * de + 4 * t * de * ff)
    self_attn = 8 * seq * d * d + 4 * seq * seq * d
    cross = 4 * seq * d * d + 4 * t * d * d + 4 * seq * t * d
    mlp = 4 * seq * d * ff
    dec = ld * (self_attn + cross + mlp)
    head = 2 * seq * d * run["vocab"]
    return float(enc + dec + head)


# ---------------------------------------------------------------- reference


def _rms(x, g, eps=1e-6):
    x32 = x.astype(jnp.float32)
    y = x32 * jax.lax.rsqrt(jnp.mean(x32 * x32, -1, keepdims=True) + eps)
    return (y * g.astype(jnp.float32)).astype(x.dtype)


def _positions(length: int, dim: int):
    """Fixed sinusoids, sin on even and cos on odd channels."""
    pos = jnp.arange(length, dtype=jnp.float32)[:, None]
    inv = jnp.exp(jnp.arange(0, dim, 2, dtype=jnp.float32)
                  * (-math.log(10000.0) / dim))
    pe = jnp.zeros((length, dim), jnp.float32)
    pe = pe.at[:, 0::2].set(jnp.sin(pos * inv))
    return pe.at[:, 1::2].set(jnp.cos(pos * inv))


def _attention(p, xq, xkv, heads: int, causal: bool):
    b, lq, _ = xq.shape
    lk = xkv.shape[1]
    q = (xq @ p["wq"]).reshape(b, lq, heads, -1)
    k = (xkv @ p["wk"]).reshape(b, lk, heads, -1)
    v = (xkv @ p["wv"]).reshape(b, lk, heads, -1)
    s = jnp.einsum("bqhd,bkhd->bhqk", q, k).astype(jnp.float32)
    s = s / math.sqrt(q.shape[-1])
    if causal:
        keep = jnp.arange(lq)[:, None] >= jnp.arange(lk)[None, :]
        s = jnp.where(keep, s, -jnp.inf)
    pr = jax.nn.softmax(s, axis=-1).astype(xq.dtype)
    o = jnp.einsum("bhqk,bkhd->bqhd", pr, v).reshape(b, lq, -1)
    return o @ p["wo"]


def _mlp(p, x):
    return jax.nn.gelu(x @ p["w_in"], approximate=True) @ p["w_out"]


def reference_loss(params: dict, batch: dict, run: dict,
                   dtype=jnp.float32):
    """(B,) mean next-token cross entropy of each example. ``dtype`` is
    the compute type of weights and activations; norms, softmax and the
    loss are taken in float32."""
    heads = run["n_heads"]
    p = jax.tree.map(lambda a: a.astype(dtype), params)

    h = batch["frames"].astype(dtype)
    h = h + _positions(h.shape[1], h.shape[2]).astype(dtype)

    def enc_layer(h, lp):
        x = _rms(h, lp["ln1"]["scale"])
        h = h + _attention(lp["attn"], x, x, heads, causal=False)
        return h + _mlp(lp["mlp"], _rms(h, lp["ln2"]["scale"])), None

    h, _ = jax.lax.scan(enc_layer, h, p["enc_layers"])
    enc = _rms(h, p["enc_ln"]["scale"])

    tok = batch["tokens"]
    y = p["embed"][tok] + _positions(tok.shape[1], run["d_model"]).astype(
        dtype)

    def dec_layer(y, lp):
        x = _rms(y, lp["ln1"]["scale"])
        y = y + _attention(lp["attn"], x, x, heads, causal=True)
        x = _rms(y, lp["ln_x"]["scale"])
        y = y + _attention(lp["xattn"], x, enc, heads, causal=False)
        return y + _mlp(lp["mlp"], _rms(y, lp["ln2"]["scale"])), None

    y, _ = jax.lax.scan(dec_layer, y, p["dec_layers"])
    logits = (_rms(y, p["dec_ln"]["scale"]) @ p["head"]).astype(jnp.float32)
    logits = logits[..., :run["vocab"]]          # the padded rows are masked
    lse = jax.nn.logsumexp(logits[:, :-1], axis=-1)
    gold = jnp.take_along_axis(logits[:, :-1], tok[:, 1:, None], -1)[..., 0]
    return jnp.mean(lse - gold, axis=-1)
