"""ClusterPlaneServer: batched personalized inference off one hot plane.

FedSPD's product is Eq. (2)'s per-user soft mixture of S cluster models.
The naive serving shape materializes one pytree per user — dead on
arrival at the ROADMAP's millions-of-users cardinality. This server holds
the packed ``(S, X)`` cluster plane hot on device ONCE and answers a
heterogeneous request batch — ``(B, S)`` mixture weights + inputs — by
contracting the weights over the plane inside the compiled program:

  fp32   u @ plane                     (one einsum)
  int8   kernels/gossip_mix_dequant    (fused dequant + mix, int8 HBM)
  int4   kernels/mixture_mix_dequant4  (fused nibble-unpack + dequant +
                                        mix, ~0.5 byte/param HBM)

The (B, X) personalized parameters exist only as an intermediate inside
the step — unpacked through the PackSpec bridge into (B,)-leaved pytrees
and consumed by a vmapped forward/decode immediately. Each entry point is
ONE jitted program: ``n_compiles`` (via the jit cache size, same
accounting as the train engines) and ``n_dispatches`` are exposed so
tests can assert one-compile/one-dispatch-per-call.

On a profiler trace (``--profile-dir``) each entry-point call is one host
span ``serve/call`` (stats ``call`` = its ``n_dispatches``, ``batch``),
holding ``serve/dispatch`` (the jitted call returning) and
``serve/block`` (the wait for the device); inside the program the mix
runs under ``serve/mix_<codec>`` and the unpack into the model's leaves
under ``serve/unpack``.
"""
from __future__ import annotations

import contextlib
import time
from typing import Any

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.packing import PackSpec, unpack
from repro.kernels.gossip_mix import (
    gossip_mix_dequant,
    int4_slab_layout,
    mixture_mix_dequant4,
)
from repro.serve.artifact import ServableArtifact
from repro.telemetry import LatencyStats, annotate, compile_count


class ClusterPlaneServer:
    """Serve personalized mixtures from one resident cluster plane.

    Construct from a loaded artifact (``from_artifact``) or directly from
    a plane in one of the shipping forms. ``bundle`` (a models/registry
    ModelBundle) enables ``generate``; ``apply_fn`` (a per-model forward
    like smallnets' classifiers, taking a (1, ...) minibatch) enables
    ``predict``.
    """

    def __init__(self, spec: PackSpec, *, codec: str = "fp32",
                 qblock: int = 64, plane=None, plane_q=None,
                 plane_scale=None, plane_packed=None, u_table=None,
                 bundle=None, apply_fn=None):
        self.spec = spec
        self.codec = codec
        self.qblock = int(qblock)
        self.bundle = bundle
        self.apply_fn = apply_fn
        self.u_table = None if u_table is None else np.asarray(
            u_table, np.float32)
        x = spec.size
        if codec == "fp32":
            if plane is None:
                raise ValueError("codec='fp32' needs plane=(S, X)")
            self.plane = jnp.asarray(plane, jnp.float32)
            if self.plane.ndim != 2 or self.plane.shape[1] != x:
                raise ValueError(
                    f"plane {self.plane.shape} is not (S, X={x})")
            self.n_clusters = int(self.plane.shape[0])
        elif codec == "int8":
            if plane_q is None or plane_scale is None:
                raise ValueError("codec='int8' needs plane_q + plane_scale")
            self.plane_q = jnp.asarray(plane_q)
            self.plane_scale = jnp.asarray(plane_scale, jnp.float32)
            self.n_clusters = int(self.plane_q.shape[0])
        elif codec == "int4":
            if plane_packed is None or plane_scale is None:
                raise ValueError(
                    "codec='int4' needs plane_packed + plane_scale")
            # re-laid once for the kernel (kernels/gossip_mix docstring)
            packed, scale = int4_slab_layout(plane_packed, plane_scale,
                                             qblock=self.qblock)
            self.plane_packed = jnp.asarray(packed)
            self.plane_scale = jnp.asarray(scale)
            self.n_clusters = int(self.plane_packed.shape[0])
        else:
            raise ValueError(
                f"codec {codec!r} is not a plane shipping format")
        self.n_dispatches = 0
        self.dequant_calls = 0
        self.latency = LatencyStats()
        self._personalized = jax.jit(self._personalized_impl)
        self._predict = jax.jit(self._predict_impl)
        self._generate = jax.jit(
            self._generate_impl,
            static_argnames=("gen", "temperature", "max_len"),
        )

    @contextlib.contextmanager
    def _call(self, batch: int):
        """One entry-point call: counts it and spans it (``serve/call``)
        from argument conversion to return."""
        self.n_dispatches += 1
        if self.codec != "fp32":
            self.dequant_calls += 1
        with annotate("serve/call", call=self.n_dispatches, batch=batch):
            yield

    def _timed(self, fn, batch: int):
        """Dispatch one entry-point batch and record its request latency
        (dispatch + device completion — what a caller actually waits)."""
        t0 = time.perf_counter()
        with annotate("serve/dispatch"):
            out = fn()
        with annotate("serve/block"):
            jax.block_until_ready(out)
        self.latency.record(time.perf_counter() - t0, batch=batch)
        return out

    @classmethod
    def from_artifact(cls, artifact: ServableArtifact, spec: PackSpec, *,
                      bundle=None, apply_fn=None) -> "ClusterPlaneServer":
        m = artifact.manifest
        if m.pack_digest is not None and m.pack_digest != spec.digest:
            raise ValueError(
                f"artifact pack_digest {m.pack_digest!r} != spec "
                f"{spec.digest!r} — wrong architecture for this plane"
            )
        return cls(
            spec, codec=m.codec, qblock=m.qblock or 64,
            plane=artifact.plane, plane_q=artifact.plane_q,
            plane_scale=artifact.plane_scale,
            plane_packed=artifact.plane_packed, u_table=artifact.u_table,
            bundle=bundle, apply_fn=apply_fn)

    # -- the Eq. (2) contraction over the resident plane (traced) --------

    def _mix(self, u: jnp.ndarray) -> jnp.ndarray:
        """(B, S) mixture weights -> (B, X) personalized flat params."""
        # named_scope, not a profiler annotation: this runs INSIDE the
        # jitted entry points, where host-side spans cannot see
        with jax.named_scope(f"serve/mix_{self.codec}"):
            x = self.spec.size
            if self.codec == "fp32":
                return jnp.einsum("bs,sx->bx", u.astype(jnp.float32),
                                  self.plane)
            if self.codec == "int8":
                out = gossip_mix_dequant(
                    u.astype(jnp.float32), self.plane_q, self.plane_scale,
                    qblock=self.qblock)
            else:  # int4
                out = mixture_mix_dequant4(
                    u.astype(jnp.float32), self.plane_packed,
                    self.plane_scale, qblock=self.qblock)
            return out[:, :x]

    # -- entry points (each ONE jitted program) --------------------------

    def _unpack(self, flat: jnp.ndarray) -> Any:
        """(B, X) -> the model's leaves, (B,)-leading: the re-layout of the
        mixed rows, scoped apart from the mix."""
        with jax.named_scope("serve/unpack"):
            return unpack(flat, self.spec)

    def _personalized_impl(self, u):
        return self._unpack(self._mix(u))

    def personalized(self, u) -> Any:
        """(B, S) -> personalized params pytree with (B,)-leading leaves."""
        with self._call(np.shape(u)[0]):
            u = jnp.asarray(u)
            return self._timed(lambda: self._personalized(u), u.shape[0])

    def _predict_impl(self, u, inputs):
        params = self._unpack(self._mix(u))

        def one(p, x):
            return self.apply_fn(p, x[None, ...])[0]

        return jax.vmap(one)(params, inputs)

    def predict(self, u, inputs) -> jnp.ndarray:
        """Personalized forward: request i's input through request i's
        mixture — mix, unpack, and the vmapped apply in one program."""
        if self.apply_fn is None:
            raise ValueError("predict needs apply_fn= at construction")
        with self._call(np.shape(u)[0]):
            u = jnp.asarray(u)
            inputs = jnp.asarray(inputs)
            return self._timed(lambda: self._predict(u, inputs), u.shape[0])

    def _generate_impl(self, u, prompts, key, *, gen, temperature, max_len):
        bundle = self.bundle
        vocab = bundle.cfg.vocab
        params = self._unpack(self._mix(u))
        lp = prompts.shape[1]

        # per-request prefill: pos lands at lp statically, so the first
        # generated token always comes from re-scoring the last prompt
        # token (same contract as the old launch/serve.generate)
        def one(p, prompt):
            cache = bundle.init_cache(1, max_len)
            cache = bundle.prefill(p, {"tokens": prompt[None, :]}, cache)
            cache = dict(cache)
            cache["pos"] = jnp.asarray(lp - 1, jnp.int32)
            logits, cache = bundle.decode_step(p, cache, prompt[None, -1:])
            return logits[0, -1, :vocab], cache

        lg0, caches = jax.vmap(one)(params, prompts)

        def sample(lg, k):
            if temperature > 0:
                tok = jax.random.categorical(k, lg / temperature)
            else:
                tok = jnp.argmax(lg, axis=-1)
            return tok.astype(jnp.int32)

        def body(carry, k):
            lg, caches = carry

            def stepf(p, c, t):
                logits, c2 = bundle.decode_step(p, c, t[None, None])
                return logits[0, -1, :vocab], c2

            tok = sample(lg, k)                       # (B,)
            lg2, caches2 = jax.vmap(stepf)(params, caches, tok)
            return (lg2, caches2), tok

        keys = jax.random.split(key, gen)
        _, toks = jax.lax.scan(body, (lg0, caches), keys)
        return toks.T                                 # (B, gen)

    def generate(self, u, prompts, *, gen: int, temperature: float = 0.0,
                 key=None) -> jnp.ndarray:
        """Batched personalized generation: B requests, each with its own
        mixture row, decoded in ONE compiled program (prefill + re-score +
        lax.scan over the gen tokens). Returns (B, gen) int32 tokens."""
        if self.bundle is None:
            raise ValueError("generate needs bundle= at construction")
        with self._call(np.shape(prompts)[0]):
            prompts = jnp.asarray(prompts, jnp.int32)
            if key is None:
                key = jax.random.PRNGKey(0)
            max_len = prompts.shape[1] + int(gen) + 1
            u = jnp.asarray(u)
            return self._timed(
                lambda: self._generate(
                    u, prompts, key, gen=int(gen),
                    temperature=float(temperature), max_len=max_len,
                ),
                prompts.shape[0],
            )

    def serve_client(self, client: int, prompts, *, gen: int,
                     temperature: float = 0.0, key=None) -> jnp.ndarray:
        """Generate for one trained client: its u-table row broadcast over
        the request batch."""
        if self.u_table is None:
            raise ValueError("serve_client needs u_table= at construction")
        row = self.u_table[int(client)]
        u = np.broadcast_to(row, (np.shape(prompts)[0], row.shape[0]))
        return self.generate(u, prompts, gen=gen, temperature=temperature,
                             key=key)

    # -- accounting (same convention as the train engines) ---------------

    @property
    def n_compiles(self) -> int:
        """Total compiled programs across the three entry points."""
        return sum(compile_count(f) for f in
                   (self._personalized, self._predict, self._generate))

    @property
    def plane_bytes(self) -> int:
        """Resident HBM footprint of the hot plane (weights + scales) —
        the plane-residency counter in the serve telemetry snapshot."""
        if self.codec == "fp32":
            return int(self.plane.size) * 4
        if self.codec == "int8":
            return int(self.plane_q.nbytes) + int(self.plane_scale.nbytes)
        return int(self.plane_packed.nbytes) + int(self.plane_scale.nbytes)

    def telemetry_snapshot(self) -> dict:
        """One JSON-able dict of the serve-path counters: codec, plane
        residency, compile/dispatch/dequant counts, and the per-batch
        latency percentiles + QPS (telemetry/events.py's
        ``serve_summary`` event; the summary renderer tables it)."""
        return {
            "codec": self.codec,
            "n_clusters": self.n_clusters,
            "plane_bytes": self.plane_bytes,
            "n_compiles": self.n_compiles,
            "n_dispatches": self.n_dispatches,
            "dequant_calls": self.dequant_calls,
            **self.latency.snapshot(),
        }
