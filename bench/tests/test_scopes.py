"""The scope reduction (``bench/scopes.py``) and the readers built on it,
on the CPU.

- the protobuf decoder against the trace recorded on a TPU v5e before
  the program had scopes: every op's ``tf_op`` and program;
- scope paths, idle-gap names and the readers on synthetic traces;
- each reader on a small traced run recorded on one TPU v5e with the
  scopes in the program: its value pinned;
- the readers read nothing, without raising, on a trace whose program has
  neither the scopes nor the server's spans.
"""
from __future__ import annotations

import json
import pathlib
from types import SimpleNamespace

import pytest

from bench import core
from bench import scopes as sc
from bench import trace as tr

DATA = pathlib.Path(__file__).parent / "data"
PROBE = DATA / "v5e_probe.xplane.pb"
DEV = "/device:TPU:0"
ROUND_READERS = ("round_local_step_ms", "round_clustering_ms",
                 "round_plane_ms", "round_attention_ms")
SERVE_READERS = ("serve_unpack_ms", "serve_dispatch_ms")


def _reader(name):
    return core.load_module(core.BENCH / "metrics" / f"{name}.py")


def _ctx(path, counters, window=None, chips=1):
    """A reader's context over a trace file, as the harness gives it."""
    sc._CACHE.clear()             # a new run: its splits are logged anew
    trace = tr.Trace.load(path)
    logged = []
    return SimpleNamespace(
        trace_path=str(path), trace=trace, counters=counters, chips=chips,
        window=window or trace.window(), log=logged.append,
        logged=logged), logged


# ---------------------------------------------------------------- decoder


def test_metadata_of_the_recorded_probe():
    meta = sc.read_metadata(PROBE)
    assert {k: len(v) for k, v in meta.items()} == {
        DEV: 2, "/device:CUSTOM:Megascale Trace": 0}
    lam, wrapped = 10611504084433719517, 6572946543831880375
    assert meta[DEV][(lam, "%convolution_reduce_fusion")] == (
        "jit(<lambda>)/dot_general:")
    assert meta[DEV][(wrapped, "%tpu_custom_call.1")] == (
        "jit(wrapped)/pallas_call:")


def test_every_probe_op_gets_its_program():
    s = sc.Scopes.load(PROBE)
    lam, wrapped = 10611504084433719517, 6572946543831880375
    assert [m[3] for m in s.modules[DEV]] == ["jit__lambda", "jit_wrapped"] * 3
    assert s.programs(DEV, sc.re.compile("jit_wrapped")) == {wrapped}
    ops = s.ops[DEV]
    assert len(ops) == 12
    by_name = {}
    for o in ops:
        by_name.setdefault(o.name, set()).add((o.program, o.scope, o.pallas))
    assert by_name == {
        "%copy-start": {(lam, "", False)},
        "%copy-done": {(lam, "", False)},
        "%convolution_reduce_fusion": {(lam, "jit(<lambda>)/dot_general",
                                        False)},
        "%tpu_custom_call.1": {(wrapped, "jit(wrapped)/pallas_call", True)},
    }
    # the same ops and clock as bench/trace.py's reduction
    t = tr.Trace.load(PROBE)
    assert [(o.start, o.end, o.name) for o in ops] == [
        (o.start, o.end, o.name) for o in t.ops[DEV]]
    marks = [(a, b) for a, b, n in t.spans if n.startswith("bench/")]
    w0, w1 = marks[0][0], marks[-1][1]
    assert s.scope_ns(DEV, lambda p: "pallas_call" in p, w0, w1) == (
        t.op_ns(DEV, lambda o: o.is_pallas, w0, w1))


# ------------------------------------------------------------ scope paths


@pytest.mark.parametrize("path,name,hit", [
    ("jit(step)/fedspd/local_step/vmap(jvp(attn))/dot_general", "attn", True),
    ("jit(step)/fedspd/local_step/vmap(transpose(jvp(attn)))/mul",
     "fedspd/local_step", True),
    ("jit(step)/fedspd/local_step/while/body/closed_call/attn/add", "attn",
     True),
    ("jit(step)/fedspd/cluster/vmap(jvp())/mul", "attn", False),
    ("jit(step)/fedspd/select/select_n", "fedspd/scatter", False),
    ("jit(step)/fedspd/local_stepper/add", "fedspd/local_step", False),
    ("jit(_personalized_impl)/serve/unpack/slice", "serve/unpack", True),
    ("", "attn", False),
])
def test_under(path, name, hit):
    assert sc.under(path, name) is hit


def test_scope_path_drops_the_op_type():
    assert sc.scope_path("jit(f)/a/dot_general:") == "jit(f)/a/dot_general"
    assert sc.scope_path("jit(f)/a/b") == "jit(f)/a/b"
    assert sc.segments("jit(f)/vmap(jvp(attn))/x") == ["f", "attn", "x"]


# ------------------------------------------------------------- synthetic


def _synthetic():
    """Two rounds of a round step (program 7), a check program (9) and
    one serve call (11), on one device, with the harness's and the
    server's host spans."""
    o = sc.ScopedOp
    r = "jit(step_stream_packed)/"
    ops = [
        o(0, 10, "%select_n.1", r + "fedspd/select/select_n", 7),
        o(10, 40, "%while.3", r + "fedspd/local_step/while", 7),
        o(15, 25, "%fusion.4", r + "fedspd/local_step/vmap(jvp(attn))/dot",
          7),
        o(40, 50, "%step_stream_packed.1", r + "pallas_call", 7, True),
        o(50, 60, "%dynamic-update-slice.2", r + "fedspd/scatter/scatter",
          7),
        o(60, 70, "%fusion.9", r + "fedspd/cluster/vmap(attn)/dot", 7),
        o(70, 75, "%reduce.1", r + "fedspd/consensus/reduce_sum", 7),
        o(75, 80, "%copy.5", "", 7),
        o(80, 90, "%fusion.1", "jit(fp)/attn/dot", 9),
        o(95, 120, "%copy.2", "jit(_personalized_impl)/serve/unpack/copy",
          11),
        o(150, 160, "%fusion.1", "jit(fp)/mul", 9),
    ]
    mods = [(0, 80, 7, "jit_step_stream_packed"), (80, 90, 9, "jit_fp"),
            (95, 120, 11, "jit__personalized_impl"), (150, 160, 9, "jit_fp")]
    spans = [(0, 200, tr.WINDOW_SPAN), (90, 150, "bench/dispatch"),
             (90, 150, "serve/call"), (90, 95, "serve/dispatch"),
             (95, 150, "serve/block"), (150, 200, "bench/wait")]
    return sc.Scopes(ops={DEV: ops}, modules={DEV: mods}, spans=spans)


def test_synthetic_round_split():
    s = _synthetic()
    progs = s.programs(DEV, sc.ROUND_PROGRAM, 0, 200)
    assert progs == {7}

    def ns(name):
        return s.ops_ns(DEV, lambda o: o.program in progs and sc.under(
            o.scope, name), 0, 200)

    assert ns("fedspd/local_step") == 30      # the while holds the fusion
    assert ns("attn") == 20                   # not the check program's
    assert ns("fedspd/select") + ns("fedspd/scatter") == 20
    assert [sc.is_exchange(o) for o in s.ops[DEV]].count(True) == 1


def test_idle_gaps_name_the_servers_innermost_span():
    gaps = _synthetic().idle_gaps(DEV, 0, 200)
    assert gaps == [["bench/wait", 40e-9],
                    ["bench/dispatch>serve/block", 30e-9],
                    ["bench/dispatch>serve/dispatch", 5e-9]]


def test_readers_on_a_synthetic_trace(monkeypatch):
    s = _synthetic()
    monkeypatch.setattr(sc, "of", lambda ctx: s)
    logged = []
    ctx = SimpleNamespace(counters={"rounds": 2, "calls": 1}, chips=1,
                          window=(0, 200), log=logged.append)
    got = {n: _reader(n).read(ctx) for n in ROUND_READERS}
    assert got == pytest.approx({
        "round_local_step_ms": 15e-6, "round_clustering_ms": 5e-6,
        "round_plane_ms": 10e-6, "round_attention_ms": 10e-6})
    split = [m for m in logged if m.startswith("round split")]
    assert len(split) == 1
    assert "exchange kernel 0.000," in split[0]
    assert "(6.25%)" in split[0]              # %copy.5: 5 of 80 busy
    assert _reader("serve_dispatch_ms").read(ctx) == pytest.approx(5e-6)
    assert any("bench/dispatch>serve/block 0.000" in m for m in logged)
    assert _reader("serve_unpack_ms").read(ctx) == pytest.approx(25e-6)
    ctx.counters = {"rounds": 2}
    assert _reader("serve_unpack_ms").read(ctx) is None


def test_readers_read_nothing_on_a_program_without_scopes():
    """The parent program's trace: no scopes, no server spans."""
    t = tr.Trace.load(PROBE)
    marks = [(a, b) for a, b, n in t.spans if n.startswith("bench/")]
    ctx, logged = _ctx(PROBE, {"rounds": 3, "calls": 3},
                       window=(marks[0][0], marks[-1][1]))
    for name in ROUND_READERS + SERVE_READERS:
        assert _reader(name).read(ctx) is None, name
    assert sum("not read" in m for m in logged) == 6


# ------------------------------------------------- recorded on one v5e

RECORDED = {
    "whisper_base.train_fp32": DATA / "v5e_small_train.xplane.pb",
    "whisper_base.serve_int4_overload": DATA / "v5e_small_serve.xplane.pb",
}


@pytest.mark.parametrize("cell", sorted(RECORDED))
def test_readers_on_a_recorded_small_run(cell):
    """Small cells (``bench/tests/small.py``) traced on one TPU v5e with
    the program's scopes and spans; the counters and the readings the
    run printed are beside the trace."""
    path = RECORDED[cell]
    want = json.loads(path.with_suffix("").with_suffix(".json").read_text())
    ctx, logged = _ctx(path, want["counters"])
    for name, value in want["metrics"].items():
        if name in ROUND_READERS + SERVE_READERS:
            assert _reader(name).read(ctx) == pytest.approx(value), name
    names = ROUND_READERS if "train" in cell else SERVE_READERS
    assert set(names) <= set(want["metrics"])
    # the split each run logs: every part of the round, or of the call
    split = [m for m in logged if " split on " in m]
    parts = (sc.ROUND_PHASES + ("exchange kernel",) if "train" in cell
             else ("serve/mix_int4", "serve/unpack"))
    assert len(split) == 1 and all(f"{p} " in split[0] for p in parts)
    if "serve" in cell:
        gaps = [m for m in logged if m.startswith("idle gaps")]
        assert gaps and "bench/dispatch>serve/" in gaps[0]
