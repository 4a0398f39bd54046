"""Mamba-2 370M (arXiv:2405.21060; state-spaces/mamba2-370m) as this
repository runs it, cut in depth, with a plain float32 reference.

``mamba2_370m.json`` beside this file holds the sizes as run, the cut and
every departure from the published model. ``build`` makes the program's
model from those sizes; ``reference_loss`` runs each layer's selective
state-space recurrence token by token (the program computes the chunked
dual form); ``flops_per_sample`` counts one forward pass from the shapes.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp


def build(sizes: dict):
    """The program's (ArchConfig, ModelBundle) for these sizes."""
    from repro.configs.base import ArchConfig
    from repro.models.registry import build_model

    cfg = ArchConfig(**sizes["run"])
    return cfg, build_model(cfg, attn_mode="blocked")


def _log_uniform(lo, hi):
    def draw(key, shape):
        return jnp.log(jax.random.uniform(key, shape, jnp.float32, lo, hi))
    return draw


def _dt_bias(key, shape):
    dt = jnp.exp(jax.random.uniform(key, shape, jnp.float32,
                                    math.log(1e-3), math.log(1e-1)))
    return dt + jnp.log(-jnp.expm1(-dt))          # softplus^-1(dt)


def init_rule(leaf):
    from bench.weights import default_rule

    name = leaf.path[-1]
    if name == "A_log":
        return ("fn", _log_uniform(1.0, 16.0))
    if name == "dt_bias":
        return ("fn", _dt_bias)
    if name == "D":
        return ("const", 1.0)
    if name == "conv_w":
        return ("normal", 0.2)
    return default_rule(leaf)


def _dims(run: dict) -> dict:
    d = run["d_model"]
    di = run["ssm_expand"] * d
    g, n = run["ssm_groups"], run["ssm_state"]
    h = di // run["ssm_headdim"]
    return {"d": d, "di": di, "g": g, "n": n, "h": h, "p": run["ssm_headdim"],
            "conv": di + 2 * g * n, "in": 2 * di + 2 * g * n + h}


def flops_per_sample(run: dict, seq: int) -> float:
    """Forward FLOPs of one example, 2 per multiply-add: per token and
    layer the in and out projections, the depthwise conv, and the
    recurrence (decay, input outer product and add: 3 H P N; readout:
    2 H P N); the LM head over the published vocabulary."""
    k = _dims(run)
    per_token_layer = (2 * k["d"] * k["in"] + 2 * run["ssm_conv"] * k["conv"]
                       + 5 * k["h"] * k["p"] * k["n"] + 2 * k["di"] * k["d"])
    head = 2 * k["d"] * run["vocab"]
    return float(seq * (run["n_layers"] * per_token_layer + head))


# ---------------------------------------------------------------- reference


def _rms(x, g, eps=1e-6):
    x32 = x.astype(jnp.float32)
    y = x32 * jax.lax.rsqrt(jnp.mean(x32 * x32, -1, keepdims=True) + eps)
    return (y * g.astype(jnp.float32)).astype(x.dtype)


def _layer(p, h, k: dict, conv: int):
    """One Mamba-2 block with its residual. h: (B, L, D)."""
    b, l, _ = h.shape
    zxbcdt = _rms(h, p["ln"]["scale"]) @ p["in_proj"]
    z = zxbcdt[..., :k["di"]]
    xbc = zxbcdt[..., k["di"]:k["di"] + k["conv"]]
    dt_raw = zxbcdt[..., k["di"] + k["conv"]:]
    # causal depthwise conv of width ``conv`` over time, then SiLU
    pad = jnp.pad(xbc, ((0, 0), (conv - 1, 0), (0, 0)))
    xbc = sum(pad[:, i:i + l] * p["conv_w"][i] for i in range(conv))
    xbc = jax.nn.silu(xbc + p["conv_b"])
    x = xbc[..., :k["di"]].reshape(b, l, k["h"], k["p"])
    bm = xbc[..., k["di"]:k["di"] + k["g"] * k["n"]].reshape(b, l, k["g"],
                                                             k["n"])
    cm = xbc[..., k["di"] + k["g"] * k["n"]:].reshape(b, l, k["g"], k["n"])
    rep = k["h"] // k["g"]
    bm = jnp.repeat(bm, rep, axis=2).astype(jnp.float32)     # (B, L, H, N)
    cm = jnp.repeat(cm, rep, axis=2).astype(jnp.float32)
    dt = jax.nn.softplus(dt_raw.astype(jnp.float32)
                         + p["dt_bias"].astype(jnp.float32))  # (B, L, H)
    a = -jnp.exp(p["A_log"].astype(jnp.float32))              # (H,)
    xf = x.astype(jnp.float32)

    def step(state, t):                                        # (B, H, P, N)
        xt, bt, ct, dtt = t
        state = (jnp.exp(dtt * a)[..., None, None] * state
                 + (dtt[..., None] * xt)[..., None] * bt[:, :, None, :])
        return state, jnp.einsum("bhpn,bhn->bhp", state, ct)

    s0 = jnp.zeros((b, k["h"], k["p"], k["n"]), jnp.float32)
    seq = (jnp.moveaxis(xf, 1, 0), jnp.moveaxis(bm, 1, 0),
           jnp.moveaxis(cm, 1, 0), jnp.moveaxis(dt, 1, 0))
    _, y = jax.lax.scan(step, s0, seq)
    y = jnp.moveaxis(y, 0, 1) + xf * p["D"].astype(jnp.float32)[:, None]
    y = y.reshape(b, l, k["di"]).astype(h.dtype)
    y = _rms(y * jax.nn.silu(z), p["gate_ln"]["scale"])
    return h + y @ p["out_proj"]


def reference_loss(params: dict, batch: dict, run: dict,
                   dtype=jnp.float32):
    """(B,) mean next-token cross entropy of each example. ``dtype`` is
    the compute type of weights and activations; norms, the recurrence and
    the loss are taken in float32."""
    k = _dims(run)
    p = jax.tree.map(lambda a: a.astype(dtype), params)
    tok = batch["tokens"]
    h = p["embed"][tok]

    # each layer's token-by-token states are recomputed in the backward
    # pass rather than kept (20 layers x 128 tokens x (H, P, N) would not
    # fit beside the model)
    @jax.checkpoint
    def body(h, lp):
        return _layer(lp, h, k, run["ssm_conv"]), None

    h, _ = jax.lax.scan(body, h, p["layers"])
    logits = (_rms(h, p["ln_f"]["scale"]) @ p["head"]).astype(jnp.float32)
    logits = logits[..., :run["vocab"]]
    lse = jax.nn.logsumexp(logits[:, :-1], axis=-1)
    gold = jnp.take_along_axis(logits[:, :-1], tok[:, 1:, None], -1)[..., 0]
    return jnp.mean(lse - gold, axis=-1)
