"""A plain FedSPD round (stream regime) for the correctness check.

Written from the paper's Algorithm 1 as the repository runs it, with
nothing imported from the program: every client i draws its cluster
s_i ~ Categorical(u_i) from the round's key, assigns each example of its
fresh batch to the cluster whose model has the lowest loss, takes tau SGD
steps on its selected model over the examples assigned to s_i, and
averages the result with the neighbours that selected the same cluster
(Eq. (1), closed neighbourhood); u moves towards the batch's assignment
fractions. Parameters are nested dicts with leaves (S, N, ...).

The keys follow the algorithm's schedule: ``split(key, 3)`` gives the
round's next key, the selection key and the local-step key; the next key
is split once more for the privacy noise (unused here) and, with a
compressing codec, for the codec's rounding. The reference draws from it
with ``jax.random`` itself; it takes no key or selection from the program.

An assignment is a discrete choice: where the two lowest losses lie within
``tie`` of each other, rounding decides it, and the reference then takes
the choice the program made (read from the program's ``u``) and counts no
disagreement. Everywhere else a different choice is a disagreement.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp


def _at(tree, idx):
    return jax.tree.map(lambda l: l[idx], tree)


def _round_dtype(tree, dtype):
    return jax.tree.map(lambda l: l.astype(dtype).astype(jnp.float32), tree)


def quantize(msg, uni, *, bits: int, block: int):
    """Blockwise symmetric quantization with stochastic rounding, as the
    wire codec is specified: each block of ``block`` values is scaled by
    its largest magnitude over 2**(bits-1) - 1, and every value rounds down
    after adding its uniform draw ``uni`` (nq, block) in [0, 1). ``msg`` is
    one client's (X,) message; returns what the receiver decodes."""
    x = msg.shape[0]
    nq = uni.shape[0]
    xb = jnp.pad(msg, (0, nq * block - x)).reshape(nq, block)
    qmax = float(2 ** (bits - 1) - 1)
    scale = jnp.max(jnp.abs(xb), axis=-1) / qmax
    y = xb / jnp.maximum(scale, 1e-12)[:, None]
    q = jnp.clip(jnp.floor(y + uni), -qmax, qmax)
    return (q * scale[:, None]).reshape(-1)[:x]


def make_round(loss_fn, *, n_clusters: int, tau: int, lr0: float,
               lr_decay: float, u_ema: float, adj, tie: float,
               state_dtype=jnp.float32, u_floor: float = 1e-3, codec=None):
    """Returns jitted round(params, u, key, ef, rnd, batch, zb_prog) ->
    (params, u, key, ef, s, disagreements, unit2): ``unit2`` holds, per
    leaf, the largest squared norm over clients of the step lr * g that
    the whole batch's gradient would take. ``codec`` ({"bits", "block"}
    or None) sends each client's updated model through the quantizer with
    its error-feedback residual ``ef`` (N, X) added before and kept after.
    ``params`` and ``ef`` are donated. ``loss_fn(params, batch)``
    gives the (B,) per-example losses of one model; ``state_dtype`` is the
    type the parameters are held in between the steps of the round (the
    control holds them in bfloat16)."""
    adj = jnp.asarray(adj, jnp.float32)

    def round_fn(params, u, key, ef, rnd, batch, zb_prog):
        n = u.shape[0]
        key, k_sel, k_local = jax.random.split(key, 3)
        del k_local  # the stream regime's steps draw nothing
        lr = lr0 * lr_decay ** rnd.astype(jnp.float32)
        s = jax.random.categorical(k_sel, jnp.log(jnp.maximum(u, 1e-12)),
                                   axis=-1)
        params = _round_dtype(params, state_dtype)

        # clustering: each example to the cluster of lowest loss
        losses = jax.vmap(lambda pc: jax.vmap(loss_fn)(pc, batch))(params)
        zb_ref = jnp.argmin(losses, axis=0)                   # (N, B)
        srt = jnp.sort(losses, axis=0)
        margin = srt[1] - srt[0]
        tied = margin < tie
        zb = jnp.where(tied, zb_prog, zb_ref) if zb_prog is not None \
            else zb_ref
        disagree = (jnp.sum((~tied) & (zb_prog != zb_ref))
                    if zb_prog is not None else jnp.zeros((), jnp.int32))
        mask = (zb == s[:, None]).astype(jnp.float32)

        # tau SGD steps on the selected model over the examples of s_i,
        # one client at a time (the reference keeps one client's gradient)
        def mean_loss(p, b):
            return jnp.mean(loss_fn(p, b))

        def masked(p, b, m):
            return jnp.sum(loss_fn(p, b) * m) / jnp.maximum(jnp.sum(m), 1.0)

        unit2, cs = None, []
        for i in range(n):
            c, b = _at(params, (s[i], i)), _at(batch, i)
            for _ in range(tau):
                # the whole batch's step sets the scale the check reads in
                g_all = jax.grad(mean_loss)(c, b)
                sq = jnp.stack([jnp.sum(jnp.square(lr * l))
                                for l in jax.tree.leaves(g_all)])
                unit2 = sq if unit2 is None else jnp.maximum(unit2, sq)
                if mask.shape[1] == 1:    # one example: its mask scales it
                    g = jax.tree.map(lambda l: l * mask[i, 0], g_all)
                else:
                    g = jax.grad(masked)(c, b, mask[i])
                c = _round_dtype(jax.tree.map(lambda a, d: a - lr * d, c, g),
                                 state_dtype)
            cs.append(c)

        if codec is None:
            key, _k_dp = jax.random.split(key)
        else:
            key, _k_dp, k_comm = jax.random.split(key, 3)
            leaves0, tdef = jax.tree.flatten(cs[0])
            x = sum(l.size for l in leaves0)
            nq = -(-x // codec["block"])
            uni = jax.random.uniform(k_comm, (n, nq, codec["block"]))
            efs = []
            for i in range(n):
                leaves = jax.tree.leaves(cs[i])
                msg = jnp.concatenate([l.reshape(-1) for l in leaves]) + ef[i]
                sent = quantize(msg, uni[i], **codec)
                efs.append(msg - sent)
                parts, off = [], 0
                for l in leaves:
                    parts.append(sent[off:off + l.size].reshape(l.shape))
                    off += l.size
                cs[i] = jax.tree.unflatten(tdef, parts)
            ef = jnp.stack(efs)

        # Eq. (1): average with the neighbours that selected the same cluster
        w = adj * (s[None, :] == s[:, None]).astype(jnp.float32)
        w = w.at[jnp.arange(n), jnp.arange(n)].set(1.0)
        w = w / jnp.sum(w, axis=1, keepdims=True)
        for i in range(n):
            mixed = jax.tree.map(lambda *ls: sum(w[i, j] * ls[j]
                                                 for j in range(n)), *cs)
            mixed = _round_dtype(mixed, state_dtype)
            params = jax.tree.map(lambda l, m: l.at[s[i], i].set(m),
                                  params, mixed)

        frac = jnp.mean(jax.nn.one_hot(zb, n_clusters), axis=1)   # (N, S)
        frac = jnp.maximum(frac, u_floor)
        frac = frac / jnp.sum(frac, axis=1, keepdims=True)
        u = (1 - u_ema) * u + u_ema * frac
        return params, u, key, ef, s, disagree, unit2

    return jax.jit(round_fn, donate_argnums=(0, 3))


def assignments_from_u(u_new, u_old, u_ema: float):
    """The program's per-client assignment of a one-example batch, read
    back from its u: the batch fraction is one-hot (before the floor)."""
    frac = (u_new - (1 - u_ema) * u_old) / u_ema
    return jnp.argmax(frac, axis=-1)[:, None]
