"""Telemetry subsystem (src/repro/telemetry/).

PR-9 acceptance criteria: (a) every traced round-metric stream is
bit-identical between the Python-loop and lax.scan engines — including
under the fully composed scenario (heterogeneity + dropout + cohort
subsampling + int8 codec); (b) collection is compile/dispatch-neutral:
a scan-rolled run with telemetry ON still reports exactly one compile
and one dispatch, and the training result is bitwise unchanged vs
telemetry off; (c) the JSONL event log round-trips every float exactly;
(d) the serve path exposes latency/QPS/dequant/plane-residency counters.
"""
import dataclasses
import json
import warnings

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.comm.codecs import Channel, CommConfig, int4_pack
from repro.configs.paper_cnn import PaperExpConfig
from repro.core.packing import make_pack_spec, pack
from repro.data.synthetic import make_mixture_classification
from repro.experiments import (
    ClientSystemModel,
    RunConfig,
    Scenario,
    TelemetryConfig,
    run_method,
    run_method_batch,
)
from repro.models.smallnets import make_classifier
from repro.telemetry import (
    STREAMS,
    LatencyStats,
    compile_count,
    effective_degree,
    inactive_count,
    mixture_drift,
    mixture_entropy,
    read_events,
    run_events,
    spectral_gap_proxy,
    staleness_histogram,
    streams_from_events,
    summary_table,
    write_run_jsonl,
)
from repro.telemetry.metrics import consensus_residual, flatten_centers


@pytest.fixture(scope="module")
def setup():
    exp = PaperExpConfig(n_clients=6, n_per_client=32, rounds=4, tau=1,
                         batch=8, avg_degree=3.0, model="mlp", dim=8,
                         n_classes=3)
    data = make_mixture_classification(
        n_clients=6, n_clusters=2, n_per_client=32, dim=8, n_classes=3,
        seed=7, noise=0.3,
    )
    return exp, data


def _assert_streams_equal(a, b):
    assert sorted(a.telemetry["streams"]) == sorted(b.telemetry["streams"])
    for name, v in a.telemetry["streams"].items():
        np.testing.assert_array_equal(
            v, b.telemetry["streams"][name], err_msg=name)


# ------------------------------------------------------------------
# metric units
# ------------------------------------------------------------------


def test_mixture_entropy_bounds():
    n, s = 5, 4
    uniform = jnp.full((n, s), 1.0 / s)
    hard = jax.nn.one_hot(jnp.arange(n) % s, s)
    np.testing.assert_allclose(mixture_entropy(uniform), np.log(s),
                               rtol=1e-6)
    np.testing.assert_allclose(mixture_entropy(hard), 0.0, atol=1e-7)


def test_mixture_drift_zero_and_positive():
    u = jnp.asarray(np.random.default_rng(0).dirichlet(
        np.ones(3), size=6), jnp.float32)
    assert float(mixture_drift(u, u)) == 0.0
    assert float(mixture_drift(u, u * 0.5)) > 0.0


def test_consensus_residual_zero_at_consensus():
    plane = jnp.broadcast_to(jnp.arange(7.0), (2, 4, 7))  # all clients equal
    np.testing.assert_allclose(consensus_residual(plane), np.zeros(2),
                               atol=1e-7)


def test_effective_degree_complete_and_empty():
    n = 6
    full = jnp.ones((n, n))
    assert float(effective_degree(full)) == n - 1
    assert float(effective_degree(jnp.zeros((n, n)))) == 0.0


def test_spectral_gap_complete_graph_beats_ring():
    n = 8
    complete = np.ones((n, n)) - np.eye(n)
    ring = np.zeros((n, n))
    for i in range(n):
        ring[i, (i + 1) % n] = ring[i, (i - 1) % n] = 1.0
    g_complete = float(spectral_gap_proxy(jnp.asarray(complete)))
    g_ring = float(spectral_gap_proxy(jnp.asarray(ring)))
    assert 0.0 < g_ring < g_complete <= 1.0
    # empty graph: everyone isolated, no mixing, gap 0
    assert float(spectral_gap_proxy(jnp.zeros((n, n)))) == 0.0


def test_staleness_histogram_counts_and_overflow():
    stale = jnp.asarray([0, 0, 1, 2, 7, 9], jnp.int32)
    h = staleness_histogram(stale, bins=4)
    np.testing.assert_array_equal(h, [2, 1, 1, 2])  # >=3 overflows
    assert float(h.sum()) == 6


def test_inactive_count():
    w = jnp.asarray([0.0, 0.5, 1.0, 0.0])
    assert float(inactive_count(w)) == 2.0


def test_flatten_centers_pytree_and_plane():
    centers = {"a": jnp.ones((2, 3, 4)), "b": jnp.zeros((2, 3, 5, 2))}
    plane = flatten_centers(centers)
    assert plane.shape == (2, 3, 14)
    packed = jnp.ones((2, 3, 9))
    assert flatten_centers(packed) is packed


def test_compile_count_on_jitted_fn():
    f = jax.jit(lambda x: x * 2)
    assert compile_count(f) == 0
    f(jnp.ones(3))
    assert compile_count(f) == 1
    f(jnp.ones(3))
    assert compile_count(f) == 1
    with pytest.raises(AttributeError):   # only jitted callables count
        compile_count(object())


def test_latency_stats_percentiles_and_qps():
    st = LatencyStats()
    for ms in (1, 2, 3, 4, 100):
        st.record(ms / 1e3, batch=2)
    snap = st.snapshot()
    assert snap["batches"] == 5 and snap["requests"] == 10
    assert snap["p50_ms"] == pytest.approx(3.0)
    assert snap["p99_ms"] == pytest.approx(100.0)
    assert snap["qps"] > 0


# ------------------------------------------------------------------
# engine parity + compile/dispatch neutrality
# ------------------------------------------------------------------


def test_streams_bit_identical_loop_vs_scan(setup):
    exp, data = setup
    cfg = RunConfig(eval_every=2, telemetry=TelemetryConfig())
    loop = run_method("fedspd", data, exp, seed=0, cfg=cfg)
    scan = run_method("fedspd", data, exp, seed=0,
                      cfg=dataclasses.replace(cfg, scan_rounds=True))
    assert sorted(loop.telemetry["streams"]) == sorted(STREAMS)
    assert loop.telemetry["rounds"] == exp.rounds
    _assert_streams_equal(loop, scan)
    # ACCEPTANCE: telemetry ON keeps the scan engine at one compile and
    # one dispatch, and the loop engine at one compile
    assert scan.extras["n_compiles"] == 1
    assert scan.extras["n_dispatches"] == 1
    assert loop.extras["n_compiles"] == 1
    assert loop.extras["n_dispatches"] == exp.rounds


def test_streams_parity_fully_composed(setup):
    """het + dropout + cohort + int8 codec + error feedback, both
    engines: every stream (including the staleness histogram and the
    inactive count) is bit-identical."""
    exp, data = setup
    het = ClientSystemModel(
        slow_fraction=0.34, slow_factor=4.0, time_budget=1.5, jitter=0.3,
        p_unavailable=0.2, staleness_gamma=0.7, seed=11,
    )
    cfg = RunConfig(
        param_plane=True, eval_every=2, cohort_size=4,
        scenario=Scenario(dropout=0.2, seed=11, system=het),
        comm=CommConfig(codec="int8", error_feedback=True),
        telemetry=TelemetryConfig(),
    )
    loop = run_method("fedspd", data, exp, seed=0, cfg=cfg)
    scan = run_method("fedspd", data, exp, seed=0,
                      cfg=dataclasses.replace(cfg, scan_rounds=True))
    _assert_streams_equal(loop, scan)
    assert scan.extras["n_compiles"] == 1
    assert scan.extras["n_dispatches"] == 1
    # the heterogeneity streams actually fired
    assert float(np.sum(loop.telemetry["streams"]["n_inactive"])) > 0
    hist = loop.telemetry["streams"]["stale_hist"]
    np.testing.assert_allclose(hist.sum(axis=-1),
                               np.full(exp.rounds, exp.n_clients))
    # wire bytes reflect the int8 codec: below logical on every round
    # that moved bytes at all (an all-inactive round moves zero of both)
    s = loop.telemetry["streams"]
    moved = s["logical_bytes"] > 0
    assert moved.any()
    assert np.all(s["wire_bytes"][moved] < s["logical_bytes"][moved])
    np.testing.assert_array_equal(loop.extras["staleness"],
                                  scan.extras["staleness"])


def test_logical_bytes_under_cohort_dropout_and_heterogeneity(setup):
    """The round's logical-bytes counter under the fully composed
    scenario, with clients that are mostly available: it charges the
    links that exchanged, and the int8 wire bytes stay below it. (With the
    harsher system model above, by the fourth round every client has timed
    out or is unavailable, and no active pair of neighbours selected the
    same cluster in any round, so that run moves no bytes at all.)"""
    exp, data = setup
    het = ClientSystemModel(p_unavailable=0.1, staleness_gamma=0.7, seed=11)
    cfg = RunConfig(
        param_plane=True, eval_every=2, cohort_size=4,
        scenario=Scenario(dropout=0.2, seed=11, system=het),
        comm=CommConfig(codec="int8", error_feedback=True),
        telemetry=TelemetryConfig(),
    )
    s = run_method("fedspd", data, exp, seed=0, cfg=cfg).telemetry["streams"]
    moved = s["logical_bytes"] > 0
    assert moved.any()
    assert np.all(s["wire_bytes"][moved] < s["logical_bytes"][moved])
    assert np.all(s["logical_bytes"][~moved] == 0)
    assert np.all(s["wire_bytes"][~moved] == 0)


def test_telemetry_on_does_not_change_training(setup):
    exp, data = setup
    for scan_rounds in (False, True):
        cfg = RunConfig(eval_every=2, scan_rounds=scan_rounds)
        off = run_method("fedspd", data, exp, seed=0, cfg=cfg)
        on = run_method(
            "fedspd", data, exp, seed=0,
            cfg=dataclasses.replace(cfg, telemetry=TelemetryConfig()))
        np.testing.assert_array_equal(off.acc_per_client, on.acc_per_client)
        np.testing.assert_array_equal(np.asarray(off.extras["u"]),
                                      np.asarray(on.extras["u"]))
        assert off.extras["n_compiles"] == on.extras["n_compiles"]
        assert off.extras["n_dispatches"] == on.extras["n_dispatches"]
        # telemetry without a system model still reports staleness — the
        # all-zeros counters, identically from both engines
        np.testing.assert_array_equal(
            on.extras["staleness"], np.zeros(exp.n_clients, np.int32))
        assert off.telemetry is None and on.telemetry is not None


def test_batched_runs_slice_streams_per_seed(setup):
    exp, data = setup
    cfg = RunConfig(eval_every=2, telemetry=TelemetryConfig())
    loop = run_method_batch("fedspd", data, exp, seeds=(0, 1), cfg=cfg)
    scan = run_method_batch("fedspd", data, exp, seeds=(0, 1),
                            cfg=dataclasses.replace(cfg, scan_rounds=True))
    assert scan[0].extras["n_compiles"] == 1
    for a, b in zip(loop, scan):
        _assert_streams_equal(a, b)
    for r in loop:
        assert r.telemetry["streams"]["u_entropy"].shape == (exp.rounds,)
        assert r.telemetry["streams"]["consensus"].shape == (exp.rounds, 2)
    # seeds actually differ (drift depends on the per-seed key stream)
    assert not np.array_equal(loop[0].telemetry["streams"]["u_drift"],
                              loop[1].telemetry["streams"]["u_drift"])


def test_telemetry_disabled_config_is_off(setup):
    exp, data = setup
    r = run_method("fedspd", data, exp, seed=0,
                   cfg=RunConfig(eval_every=2,
                                 telemetry=TelemetryConfig(
                                     round_metrics=False)))
    assert r.telemetry is None


def test_telemetry_config_validates():
    with pytest.raises(ValueError):
        TelemetryConfig(power_iters=0)
    with pytest.raises(ValueError):
        TelemetryConfig(staleness_bins=1)


def test_pytree_engine_reports_nan_consensus(setup):
    """The per-leaf pytree engine has no packed plane; the consensus
    stream degrades to NaN instead of failing the run."""
    exp, data = setup
    r = run_method("fedspd", data, exp, seed=0,
                   cfg=RunConfig(eval_every=2, param_plane=False,
                                 telemetry=TelemetryConfig()))
    # fedspd pytree centers still expose the (S, N, ...) leaf structure,
    # so consensus may be real; the local baseline has no u at all
    r2 = run_method("local", data, exp, seed=0,
                    cfg=RunConfig(eval_every=2,
                                  telemetry=TelemetryConfig()))
    assert np.all(np.isnan(r2.telemetry["streams"]["u_entropy"]))
    assert r.telemetry is not None


# ------------------------------------------------------------------
# JSONL event log: write -> parse -> identical floats
# ------------------------------------------------------------------


def test_jsonl_round_trip_exact(setup, tmp_path):
    exp, data = setup
    r = run_method("fedspd", data, exp, seed=0,
                   cfg=RunConfig(eval_every=2, scan_rounds=True,
                                 telemetry=TelemetryConfig()))
    path = tmp_path / "telemetry.jsonl"
    write_run_jsonl(str(path), r, meta={"seed": 0})
    events = read_events(str(path))
    kinds = [e["event"] for e in events]
    assert kinds[0] == "run_meta" and kinds[-1] == "summary"
    assert kinds.count("round") == exp.rounds
    parsed = streams_from_events(events)
    for name, orig in r.telemetry["streams"].items():
        # float32 -> JSON -> float64 widens exactly: bit-identical values
        np.testing.assert_array_equal(
            parsed[name], np.asarray(orig, np.float64), err_msg=name)
    summary = events[-1]
    assert summary["n_compiles"] == 1 and summary["n_dispatches"] == 1
    assert summary["mean_acc"] == r.mean_acc
    # every line is valid standalone JSON
    with open(path) as f:
        for line in f:
            json.loads(line)


def test_run_events_without_telemetry_uses_curve(setup):
    exp, data = setup
    r = run_method("fedspd", data, exp, seed=0, cfg=RunConfig(eval_every=2))
    events = run_events(r)
    rounds = [e for e in events if e["event"] == "round"]
    assert [e["round"] for e in rounds] == [c[0] for c in r.curve]
    assert all("train_acc" in e for e in rounds)


def test_summary_table_renders(setup, tmp_path):
    exp, data = setup
    r = run_method("fedspd", data, exp, seed=0,
                   cfg=RunConfig(eval_every=2,
                                 telemetry=TelemetryConfig()))
    path = tmp_path / "t.jsonl"
    write_run_jsonl(str(path), r, meta={"seed": 0, "n_clients": 6})
    table = summary_table(read_events(str(path)))
    assert "| stream |" in table
    for name in STREAMS:
        assert f"| {name} |" in table
    assert "n_compiles=1" in table


# ------------------------------------------------------------------
# serve-path telemetry
# ------------------------------------------------------------------


def _mlp_server(codec="fp32", s=3, dim=16, qb=16):
    from repro.serve import ClusterPlaneServer

    key = jax.random.PRNGKey(0)
    _, apply, *_ = make_classifier("mlp", key, dim, 4)

    def model_init(k):
        return make_classifier("mlp", k, dim, 4)[0]

    spec = make_pack_spec(jax.eval_shape(model_init, key))
    plane = jnp.stack([pack(model_init(jax.random.PRNGKey(i)), spec)
                       for i in range(s)])
    if codec == "fp32":
        return ClusterPlaneServer(spec, plane=plane, apply_fn=apply), spec
    ch = Channel(CommConfig(codec=codec, block=qb), spec.size)
    enc = ch.encode(plane, key, rounding="nearest")
    kw = ({"plane_q": enc["q"]} if codec == "int8"
          else {"plane_packed": int4_pack(enc["q"])})
    return ClusterPlaneServer(spec, codec=codec, qblock=qb,
                              plane_scale=enc["scale"], apply_fn=apply,
                              **kw), spec


def test_serve_latency_and_residency_counters():
    server, spec = _mlp_server()
    rng = np.random.default_rng(0)
    u = rng.dirichlet(np.ones(3), size=5).astype(np.float32)
    x = rng.normal(size=(5, 16)).astype(np.float32)
    server.predict(u, x)
    server.predict(u, x)
    snap = server.telemetry_snapshot()
    assert snap["n_dispatches"] == 2 and snap["n_compiles"] == 1
    assert snap["dequant_calls"] == 0          # fp32: einsum path
    assert snap["batches"] == 2 and snap["requests"] == 10
    assert snap["p50_ms"] > 0 and snap["qps"] > 0
    assert snap["p95_ms"] >= snap["p50_ms"]
    assert snap["plane_bytes"] == 3 * spec.size * 4
    json.dumps(snap)                           # JSON-able as-is


def test_serve_dequant_counter_and_smaller_residency():
    server, spec = _mlp_server(codec="int8")
    rng = np.random.default_rng(1)
    u = rng.dirichlet(np.ones(3), size=4).astype(np.float32)
    x = rng.normal(size=(4, 16)).astype(np.float32)
    server.predict(u, x)
    snap = server.telemetry_snapshot()
    assert snap["dequant_calls"] == 1
    assert snap["plane_bytes"] < 3 * spec.size * 4   # int8 < fp32 resident


# ------------------------------------------------------------------
# deprecation shims blame the caller (stacklevel)
# ------------------------------------------------------------------


def test_legacy_kwargs_warning_names_this_file(setup):
    exp, data = setup
    small = dataclasses.replace(exp, rounds=1)
    with warnings.catch_warnings(record=True) as rec:
        warnings.simplefilter("always")
        run_method("fedspd", data, small, seed=0, eval_every=5)
    dep = [w for w in rec if issubclass(w.category, DeprecationWarning)]
    assert dep and dep[0].filename == __file__


def test_legacy_generate_shim_warning_names_this_file():
    from repro.configs.base import get_smoke_config
    from repro.launch.serve import generate
    from repro.models.registry import build_model

    cfg = get_smoke_config("olmo-1b")
    bundle = build_model(cfg, attn_mode="ref")
    params = bundle.init(jax.random.PRNGKey(0))
    prompts = jnp.zeros((1, 4), jnp.int32)
    with warnings.catch_warnings(record=True) as rec:
        warnings.simplefilter("always")
        generate(bundle, params, prompts, gen_len=2, max_len=8)
    dep = [w for w in rec if issubclass(w.category, DeprecationWarning)]
    assert dep and dep[0].filename == __file__
