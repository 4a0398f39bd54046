"""Faults planted under the timed path, to show that the correctness check
fails each one. Used by ``control.py --faults`` on the chip and by the
tests under ``bench/tests`` on the CPU; the benchmark's runs never plant
them.

Training (``train_step(kind, rig)``):
- ``unchanged``: the step returns its state as it came in;
- ``half_batch``: the second half of the clients' examples are left out
  and the first half's counted in their place;
- ``no_exchange``: the exchange is left out: each client keeps its own
  updated model.

Serving (``serve_call(kind, server)``):
- ``half_batch``: the second half of each dispatch's rows are not
  computed (left at zero);
- ``altered``: each answer goes to the wrong request (rows rolled by one).
"""
from __future__ import annotations

FAULTS = {
    "train": ("unchanged", "half_batch", "no_exchange"),
    "serve": ("half_batch", "altered"),
}


def train_step(kind: str, rig):
    import jax
    import jax.numpy as jnp

    from repro.launch import steps

    real = rig.step
    if kind == "unchanged":
        def step(state, batch):
            _, m = real(jax.tree.map(jnp.copy, state), batch)
            return state, m
        return step
    if kind == "half_batch":
        def step(state, batch):
            half = rig.n // 2

            def fold(a):
                return a.at[half:].set(a[:a.shape[0] - half])
            return real(state, jax.tree.map(fold, batch))
        return step
    if kind == "no_exchange":
        if rig.channel is None:
            def keep(c_sel, s, adj=None):
                return c_sel
        else:
            ch = rig.channel

            def keep(c_sel, s, key, ef, adj=None):
                x_hat, ef = ch.roundtrip(c_sel, key, ef)
                return x_hat.astype(c_sel.dtype), ef
            keep.comm_aware = True
        return steps.make_fedspd_train_step(
            rig.bundle, rig.gossip, rig.fcfg, mix_fn=keep,
            pack_spec=rig.spec, donate=True, comm=rig.comm)
    raise ValueError(f"unknown training fault {kind!r}")


def serve_call(kind: str, server):
    """A stand-in for ``server.personalized`` with the fault planted."""
    import jax
    import jax.numpy as jnp

    real = server.personalized
    if kind == "half_batch":
        def call(u):
            u = jnp.asarray(u)
            half = u.shape[0] // 2
            return real(u.at[half:].set(0.0))
        return call
    if kind == "altered":
        def call(u):
            out = real(u)
            return jax.tree.map(lambda a: jnp.roll(a, 1, axis=0), out)
        return call
    raise ValueError(f"unknown serving fault {kind!r}")
