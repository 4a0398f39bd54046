"""Weights from the seed, made by the benchmark and not by the program.

The program is handed a packed (S, N, X) plane in its own leaf order
(``jax.tree.flatten`` of the model's parameter tree, the order its
``PackSpec`` uses); the reference reads the same plane back leaf by leaf
through ``split``. Both sides therefore start from identical numbers,
drawn in one jitted call on the device.
"""
from __future__ import annotations

import dataclasses
import math

import jax
import jax.numpy as jnp


@dataclasses.dataclass(frozen=True)
class Leaf:
    path: tuple    # dict keys from the root, e.g. ("dec_layers", "attn", "wq")
    shape: tuple
    offset: int
    size: int


def leaf_table(shapes) -> tuple:
    """[Leaf] in flatten order from a tree of ShapeDtypeStructs."""
    flat, _ = jax.tree_util.tree_flatten_with_path(shapes)
    out, off = [], 0
    for path, sds in flat:
        keys = tuple(getattr(k, "key", getattr(k, "name", str(k)))
                     for k in path)
        size = math.prod(sds.shape) if sds.shape else 1
        out.append(Leaf(keys, tuple(sds.shape), off, size))
        off += size
    return tuple(out)


def width(table) -> int:
    return table[-1].offset + table[-1].size


def default_rule(leaf: Leaf):
    """(kind, value): norm scales 1, embeddings N(0, 0.02^2), matrices
    N(0, 1/fan_in), other vectors 0."""
    name = leaf.path[-1]
    if name == "scale":
        return ("const", 1.0)
    if name == "embed":
        return ("normal", 0.02)
    if len(leaf.shape) >= 2:
        return ("normal", 1.0 / math.sqrt(leaf.shape[-2]))
    return ("const", 0.0)


def _draw(key, leaf: Leaf, lead: tuple, rule):
    kind, v = rule(leaf)
    shape = lead + (leaf.size,)
    if kind == "const":
        return jnp.full(shape, v, jnp.float32)
    if kind == "normal":
        return v * jax.random.normal(key, shape, jnp.float32)
    if kind == "fn":            # v(key, shape) -> float32 array
        return v(key, shape)
    raise ValueError(f"unknown init kind {kind!r}")


def plane_fn(table, lead: tuple, rule=default_rule):
    """jitted key -> (*lead, X) float32 plane; every (cluster, client)
    row is an independent draw."""
    def make(key):
        parts = [_draw(jax.random.fold_in(key, i), leaf, lead, rule)
                 for i, leaf in enumerate(table)]
        return jnp.concatenate(parts, axis=-1)

    return jax.jit(make)


def change_sq_fn(table, lead: tuple, rule=default_rule):
    """jitted (plane, key) -> (*lead, L): per leaf, the sum of squares of
    the plane minus the plane ``plane_fn`` draws from ``key``. The start
    is drawn again leaf by leaf inside the reduction, so no copy of the
    whole plane is held."""
    def change(plane, key):
        return jnp.stack([
            jnp.sum(jnp.square(plane[..., l.offset:l.offset + l.size]
                               - _draw(jax.random.fold_in(key, i), l, lead,
                                       rule)), axis=-1)
            for i, l in enumerate(table)], axis=-1)

    return jax.jit(change)


def tree_change_sq_fn(table, lead: tuple, rule=default_rule):
    """The same as ``change_sq_fn`` for a nested dict of (*lead, *shape)
    leaves."""
    def change(tree, key):
        out = []
        for i, l in enumerate(table):
            node = tree
            for k in l.path:
                node = node[k]
            d = (node.reshape(lead + (l.size,))
                 - _draw(jax.random.fold_in(key, i), l, lead, rule))
            out.append(jnp.sum(jnp.square(d), axis=-1))
        return jnp.stack(out, axis=-1)

    return jax.jit(change)


def split(plane, table) -> dict:
    """(*lead, X) plane -> nested dict of (*lead, *shape) leaves."""
    lead = plane.shape[:-1]
    tree = {}
    for leaf in table:
        node = tree
        for k in leaf.path[:-1]:
            node = node.setdefault(k, {})
        node[leaf.path[-1]] = plane[..., leaf.offset:leaf.offset + leaf.size
                                    ].reshape(lead + leaf.shape)
    return tree


def leaf_sq_norms(plane, table):
    """(*lead, X) -> (*lead, L): each leaf's sum of squares, in float32."""
    return jnp.stack([jnp.sum(jnp.square(plane[..., l.offset:l.offset + l.size]
                                         .astype(jnp.float32)), axis=-1)
                      for l in table], axis=-1)
