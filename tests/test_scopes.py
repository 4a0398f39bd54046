"""Named scopes and host spans the profiler's trace carries.

A device op's scope path (the ``op_name`` of its HLO metadata, ``tf_op``
on a TPU trace) names the round-step phase it belongs to; the serve
entry points write host spans around each call. These tests read both at
small sizes on the CPU:

- the lowered round step (packed plane, stream and full regimes) has every
  ``fedspd/*`` phase scope and ``attn`` in its op metadata;
- the exchange's ``pallas_call`` sits under no ``fedspd/`` scope, and the
  serve mix kernel directly under ``serve/mix_<codec>``, so the kernels
  keep the instruction names the trace readers match them by;
- ``ClusterPlaneServer`` scopes the unpack apart from the mix, and under the
  profiler writes one ``serve/call`` span per call, holding one
  ``serve/dispatch`` and one ``serve/block``.
"""
import glob
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.comm.codecs import CommConfig, int4_pack, make_channel
from repro.core.fedspd import FedSPDConfig, FedSPDState, make_round_step
from repro.core.gossip import GossipSpec, make_mix_fn
from repro.core.packing import make_pack_spec
from repro.graphs.topology import make_graph
from repro.models.attention import ref_attention
from repro.serve import ClusterPlaneServer

PHASES = ("fedspd/select", "fedspd/cluster", "fedspd/local_step",
          "fedspd/scatter", "fedspd/consensus")
N, S, M, L, D, C = 4, 2, 8, 4, 8, 3


def _init(key):
    kw, ko = jax.random.split(key)
    return {"w": 0.3 * jax.random.normal(kw, (D, D)),
            "out": 0.3 * jax.random.normal(ko, (D, C))}


def _per_example_loss(p, batch):
    """A one-head self-attention classifier over L tokens of width D."""
    x = batch["x"].reshape(-1, L, D)
    h = (x @ p["w"])[:, :, None, :]                  # (B, L, 1, D)
    a = ref_attention(h, h, h, causal=False)[:, :, 0]
    logits = jnp.mean(a, axis=1) @ p["out"]
    return -jax.nn.log_softmax(logits)[jnp.arange(x.shape[0]), batch["y"]]


def _loss(p, batch):
    return jnp.mean(_per_example_loss(p, batch))


def _round_step(regime):
    spec = GossipSpec.from_graph(make_graph("complete", N, 3, seed=0))
    ps = make_pack_spec(jax.eval_shape(_init, jax.random.PRNGKey(0)))
    cfg = FedSPDConfig(n_clients=N, n_clusters=S, tau=1, batch=M,
                       regime=regime)
    step = make_round_step(_loss, _per_example_loss, spec, cfg,
                           mix_fn=make_mix_fn(spec, "pallas", plane=True),
                           pack_spec=ps)
    state = jax.eval_shape(lambda: _state(ps))
    x = jax.ShapeDtypeStruct((N, M, L * D), jnp.float32)
    y = jax.ShapeDtypeStruct((N, M), jnp.int32)
    data = ({"inputs": x, "targets": y} if regime == "full" else
            {"x": x, "y": y})
    return step, state, data


def _state(ps):
    return FedSPDState(
        centers=jnp.zeros((S, N, ps.size)), u=jnp.full((N, S), 0.5),
        z=jnp.zeros((N, M), jnp.int32), round=jnp.zeros((), jnp.int32),
        key=jax.random.PRNGKey(0), comm_bytes=jnp.zeros(()))


def _op_names(lowered) -> set:
    """The op_name metadata of the compiled program's instructions."""
    return set(re.findall(r'op_name="([^"]*)"',
                          lowered.compile().as_text()))


def _segments(path: str) -> list:
    """Scope path -> its segments, transform wrappers taken off:
    ``a/vmap(jvp(attn))/dot`` -> ``[a, attn, dot]``."""
    out = []
    for seg in path.split("/"):
        while re.fullmatch(r"\w+\((.*)\)", seg):
            seg = re.fullmatch(r"\w+\((.*)\)", seg).group(1)
        out.append(seg)
    return out


def _walk(jaxpr, stack=""):
    """(eqn, its whole name stack) over a jaxpr and every sub-jaxpr."""
    for e in jaxpr.eqns:
        here = "/".join(p for p in (stack, str(e.source_info.name_stack))
                        if p)
        yield e, here
        for v in e.params.values():
            for sub in (v if isinstance(v, (list, tuple)) else (v,)):
                inner = getattr(sub, "jaxpr", sub)
                if hasattr(inner, "eqns"):
                    yield from _walk(inner, here)


@pytest.mark.parametrize("regime", ["stream", "full"])
def test_round_step_op_metadata_has_every_phase_and_attn(regime):
    step, state, data = _round_step(regime)
    names = _op_names(jax.jit(step).lower(state, data))
    for phase in PHASES:
        assert any(f"/{phase}/" in n for n in names), phase
    attn = [n for n in names if "attn" in _segments(n)]
    assert attn
    # attention runs in the local step's forward and backward, and in the
    # clustering forward
    assert any("/fedspd/local_step/" in n for n in attn)
    assert any("/fedspd/cluster/" in n for n in attn)


@pytest.mark.parametrize("regime", ["stream", "full"])
def test_exchange_kernel_sits_under_no_round_step_scope(regime):
    step, state, data = _round_step(regime)
    calls = [here for e, here in _walk(jax.make_jaxpr(step)(state,
                                                            data).jaxpr)
             if e.primitive.name == "pallas_call"]
    assert len(calls) == 1
    assert "fedspd/" not in calls[0], calls[0]


def test_codec_encode_is_scoped():
    ch = make_channel(CommConfig(codec="int8", block=16,
                                 error_feedback=True), 64)
    x = jnp.ones((N, 64))
    key = jax.random.PRNGKey(0)
    for fn in (lambda x: ch.encode(x, key),
               lambda x: ch.encode_stream(x, key, jnp.zeros_like(x))):
        ops = [n for n in _op_names(jax.jit(fn).lower(x))
               if n.startswith("jit(")]
        assert ops and all("/comm/encode/" in n for n in ops), ops


def _server(codec, x=200, s=2, qblock=64):
    spec = make_pack_spec({"a": jax.ShapeDtypeStruct((x - 72,), jnp.float32),
                           "b": jax.ShapeDtypeStruct((8, 9), jnp.float32)})
    rng = np.random.default_rng(0)
    if codec == "fp32":
        return ClusterPlaneServer(spec, plane=rng.normal(size=(s, x)))
    xp = -(-x // qblock) * qblock
    q = rng.integers(-8, 8, (s, xp)).astype(np.int8)
    sc = rng.uniform(1e-3, 1e-2, (s, xp // qblock)).astype(np.float32)
    if codec == "int8":
        return ClusterPlaneServer(spec, codec="int8", qblock=qblock,
                                  plane_q=q, plane_scale=sc)
    return ClusterPlaneServer(spec, codec="int4", qblock=qblock,
                              plane_packed=np.asarray(int4_pack(q)),
                              plane_scale=sc)


@pytest.mark.parametrize("codec", ["fp32", "int8", "int4"])
def test_server_scopes_the_unpack_apart_from_the_mix(codec):
    server = _server(codec)
    u = jnp.full((3, 2), 0.5)
    names = _op_names(server._personalized.lower(u))
    assert any("/serve/unpack/" in n for n in names)
    assert not any("serve/unpack" in n and "serve/mix" in n for n in names)
    mix = f"serve/mix_{codec}"
    eqns = list(_walk(jax.make_jaxpr(server._personalized_impl)(u).jaxpr))
    if codec == "fp32":   # the einsum names its own step
        stacks = [h for e, h in eqns if e.primitive.name == "dot_general"]
        assert stacks == [f"{mix}/bs,sx->bx"]
    else:
        stacks = [h for e, h in eqns if e.primitive.name == "pallas_call"]
        assert stacks == [mix]


def _host_spans(trace_dir):
    from jax.profiler import ProfileData

    (path,) = glob.glob(f"{trace_dir}/**/*.xplane.pb", recursive=True)
    spans = []
    for plane in ProfileData.from_file(path).planes:
        if plane.name != "/host:CPU":
            continue
        for line in plane.lines:
            for ev in line.events:
                if ev.name.startswith("serve/"):
                    spans.append((ev.start_ns, ev.start_ns + ev.duration_ns,
                                  ev.name, dict(ev.stats)))
    return sorted(spans)


def test_server_writes_one_call_span_per_call(tmp_path):
    server = _server("int4")
    u = np.full((3, 2), 0.5, np.float32)
    server.personalized(u)                      # compile outside the trace
    before = server.n_dispatches
    with jax.profiler.trace(str(tmp_path)):
        for _ in range(3):
            server.personalized(u)
    spans = _host_spans(tmp_path)
    calls = [sp for sp in spans if sp[2] == "serve/call"]
    assert len(calls) == server.n_dispatches - before == 3
    for s0, s1, _, stats in calls:
        assert stats["batch"] == 3
        inner = [sp[2] for sp in spans
                 if sp[2] != "serve/call" and s0 <= sp[0] and sp[1] <= s1]
        assert inner == ["serve/dispatch", "serve/block"]
    assert [c[3]["call"] for c in calls] == list(
        range(before + 1, server.n_dispatches + 1))
    assert server.latency.snapshot()["batches"] == server.n_dispatches


@pytest.fixture
def v5e(monkeypatch):
    """A described TPU v5e chip (nothing runs) and the Pallas kernels
    lowered for Mosaic, as ``tests/test_tpu_compile.py`` compiles them."""
    import os

    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    import repro.kernels.gossip_mix as gm

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler / libtpu on this host
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    monkeypatch.setattr(gm, "resolve_interpret",
                        lambda i: False if i is None else bool(i))
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield jax.sharding.SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)


@pytest.mark.parametrize("regime", ["stream", "full"])
def test_exchange_kernel_keeps_the_round_steps_name_on_v5e(v5e, regime):
    """The trace reader of the exchange's roofline finds its kernel by
    the instruction name the jitted round step gives it."""
    step, state, data = _round_step(regime)
    put = lambda t: jax.tree.map(  # noqa: E731
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=v5e), t)
    text = jax.jit(step).lower(put(state), put(data)).compile().as_text()
    kernels = re.findall(r"^\s*(%\S+) = .*custom_call_target="
                         r'"tpu_custom_call"', text, re.M)
    assert kernels == [f"%step_{regime}_packed.1"]
