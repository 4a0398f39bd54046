"""The benchmark's own checks, on the CPU at a small size.

    PYTHONPATH=src python -m pytest -q bench/tests

- the trace reduction on a small trace recorded on a TPU v5e;
- the FLOP and byte counts the roofline and MFU metrics divide by,
  against hand counts;
- the correctness check: a sound small run passes, the control (the
  reference holding its state in bfloat16) fails, and each planted fault
  fails;
- the harness refuses a machine without a TPU and prints no result.
"""
from __future__ import annotations

import json
import math
import pathlib
import shutil
import subprocess
import sys

import pytest

from bench import core
from bench import trace as tr
from bench.tests.small import run_small, small_cell

DATA = pathlib.Path(__file__).parent / "data"


# ------------------------------------------------------------------ trace


def test_interval_arithmetic():
    assert tr.merge([(5, 7), (0, 2), (1, 3), (6, 9)]) == [(0, 3), (5, 9)]
    assert tr.clip([(0, 3), (5, 9)], 2, 6) == [(2, 3), (5, 6)]
    assert tr.subtract([(0, 10)], [(2, 3), (5, 7)]) == [(0, 2), (3, 5),
                                                        (7, 10)]
    assert tr.subtract([(0, 4), (6, 8)], [(3, 7)]) == [(0, 3), (7, 8)]
    assert tr.total([(0, 3), (5, 9)]) == 7


def _synthetic():
    op = tr.Op
    ops = {"/device:TPU:0": [
        op(0, 10, "fusion.1", "fusion.1"),
        op(10, 20, "collective-permute.2", "collective-permute.2"),
        op(15, 25, "fusion.3", "fusion.3"),
        op(40, 50, "custom-call.4", "custom-call.4 gossip_mix_flat"),
    ]}
    spans = [(0, 100, tr.WINDOW_SPAN), (30, 40, "bench/dispatch"),
             (55, 100, "bench/wait")]
    return tr.Trace(ops=ops, spans=spans)


def test_reduction_on_a_synthetic_trace():
    t = _synthetic()
    dev = "/device:TPU:0"
    assert t.window() == (0, 100)
    assert t.busy_ns(dev, 0, 100) == 35
    assert t.op_ns(dev, lambda o: o.matches(("gossip_mix_flat",)), 0,
                   100) == 10
    coll = lambda o: o.name.startswith("collective-permute")  # noqa: E731
    assert t.exposed_ns(dev, coll, 0, 100) == 5
    assert t.top_ops(dev, 0, 100, k=1) == [["fusion.1", 10e-9]]
    gaps = t.idle_gaps(dev, 0, 100)
    assert gaps[0] == ["bench/wait", 50e-9]
    assert gaps[1] == ["bench/dispatch", 15e-9]


def test_reduction_on_a_recorded_tpu_trace():
    """A trace recorded on one TPU v5e: three rounds of a bf16 matmul and
    the fp32 Pallas gossip mix at X = 2**20, each dispatch inside
    ``bench/dispatch``, each wait inside ``bench/wait`` and a 20 ms sleep
    inside ``bench/idle``."""
    t = tr.Trace.load(DATA / "v5e_probe.xplane.pb")
    marks = [(s, e) for s, e, n in t.spans if n.startswith("bench/")]
    w0, w1 = marks[0][0], marks[-1][1]
    assert t.devices == ["/device:TPU:0"]
    dev = t.devices[0]
    busy = t.busy_ns(dev, w0, w1)
    assert 0 < busy < w1 - w0
    mix = t.op_ns(dev, lambda o: o.matches(
        ('custom_call_target="tpu_custom_call"',)), w0, w1)
    assert 0 < mix < busy
    kernel = [o for o in t.ops[dev] if o.name == "%tpu_custom_call.1"][0]
    assert kernel.is_pallas and kernel.instruction == "tpu_custom_call"
    assert kernel.result == ("f32", 2 * 2**20)
    assert t.kernel_ns(dev, lambda o: True, w0, w1) == (mix, [])
    assert sum(n == "bench/dispatch" for _, _, n in t.spans) == 3
    names = [n for n, _ in t.top_ops(dev, w0, w1)]
    assert "%convolution_reduce_fusion" in names
    # the device waited longest while the host was dispatching (about
    # 80 ms each, under the profiler), then through the 20 ms sleeps
    gaps = [g[0] for g in t.idle_gaps(dev, w0, w1, k=4)]
    assert gaps == ["bench/dispatch"] * 3 + ["bench/idle"]


def test_pallas_kernels_by_instruction_name_and_result():
    """The roofline readers time one Pallas kernel by its instruction name
    and result size, and read nothing while another runs beside it."""
    from types import SimpleNamespace

    op = tr.Op
    pallas = ', custom_call_target="tpu_custom_call"'
    mix = op(0, 40, "%step_stream_packed.1",
             "%step_stream_packed.1 = f32[2,1000]{1,0:T(2,128)} "
             "custom-call(f32[2,2] %a, f32[2,1000] %b)" + pallas)
    other = op(50, 60, "%step_stream_packed.2",
               "%step_stream_packed.2 = f32[1,8,16,8]{3,2,1,0} "
               "custom-call(bf16[1,8,16,8] %q)" + pallas)
    fusion = op(60, 80, "%fusion.3", "%fusion.3 = f32[2,1000]{1,0} fusion()")
    assert mix.instruction == "step_stream_packed" and mix.is_pallas
    assert mix.result == ("f32", 2000)
    assert other.result == ("f32", 8 * 16 * 8)
    assert not fusion.is_pallas
    assert op(0, 1, "%t.1", "%t.1 = (f32[2], s32[]) tuple()").result == (
        "", 0)
    reader = core.load_module(core.BENCH / "metrics" / "mix_hbm_roofline.py")
    dev = "/device:TPU:0"
    logged = []
    ctx = SimpleNamespace(
        counters={"rounds": 2, "mix_bytes_per_round": 819.0,
                  "mix_out_elems": 2000},
        peaks={"hbm_bytes_per_s": 819e9}, chips=1, window=(0, 100),
        log=logged.append,
        trace=tr.Trace(ops={dev: [mix, fusion]}, spans=[]))
    # 2 rounds x 819 bytes at 819 GB/s = 2 ns, against 40 ns of kernel
    assert reader.read(ctx) == pytest.approx(5.0)
    ctx.trace = tr.Trace(ops={dev: [mix, other, fusion]}, spans=[])
    assert ctx.trace.kernel_ns(dev, lambda o: o.instruction ==
                               "step_stream_packed" and o.result[1] >= 2000,
                               0, 100) == (40, ["%step_stream_packed.2"])
    assert reader.read(ctx) is None
    assert "step_stream_packed.2" in logged[0]


# -------------------------------------------------------- FLOPs and bytes


def test_whisper_flops_by_hand():
    from bench.configs import whisper_base as wb

    run = {"d_model": 8, "d_ff": 32, "n_heads": 2, "encoder_d_model": 8,
           "encoder_frames": 10, "encoder_layers": 1, "n_layers": 1,
           "vocab": 100}
    seq = 4
    # encoder layer: q, k, v, o over 10 frames, scores and values, MLP
    enc = 4 * 2 * 10 * 8 * 8 + 2 * (2 * 10 * 10 * 8) + 2 * (2 * 10 * 8 * 32)
    # decoder: self attention over 4 tokens; cross attention (q, o over
    # 4 tokens, k, v over 10 frames, scores and values 4 x 10); MLP
    dec = (4 * 2 * 4 * 8 * 8 + 2 * (2 * 4 * 4 * 8)
           + 2 * (2 * 4 * 8 * 8) + 2 * (2 * 10 * 8 * 8) + 2 * (2 * 4 * 10 * 8)
           + 2 * (2 * 4 * 8 * 32))
    head = 2 * 4 * 8 * 100
    assert wb.flops_per_sample(run, seq) == enc + dec + head


def test_whisper_base_forward_flops():
    """About 101 GFLOP an example at the published widths, 64 tokens."""
    from bench.configs import whisper_base as wb

    sizes = core.load_json(core.BENCH / "configs" / "whisper_base.json")
    f = wb.flops_per_sample(sizes["run"], 64)
    assert math.isclose(f, 101_155_864_576, rel_tol=1e-12)


def test_mamba2_flops_by_hand():
    from bench.configs import mamba2_370m as mb

    run = {"d_model": 8, "n_layers": 2, "vocab": 100, "ssm_state": 4,
           "ssm_headdim": 4, "ssm_expand": 2, "ssm_conv": 4,
           "ssm_groups": 1}
    seq = 3
    # d_inner 16, 4 heads of 4, in_proj width 2*16 + 2*4 + 4 = 44,
    # conv channels 16 + 8 = 24
    per_layer = (2 * 8 * 44 + 2 * 4 * 24 + 5 * 4 * 4 * 4 + 2 * 16 * 8)
    assert mb.flops_per_sample(run, seq) == seq * (2 * per_layer
                                                   + 2 * 8 * 100)


def test_serve_bytes_and_flops_by_hand():
    """What serve_mix_hbm_roofline and serve_mfu divide by, for S=2
    clusters of X=1024 at qblock 64 and 8 rows a call."""
    from bench.drivers import serve

    # plane 2 x 512 bytes, 2 x 16 scales of 4 bytes, 8 x 1024 x 4 written
    assert serve.call_bytes(1024, 2, 8, 64) == 1024 + 128 + 32768
    # 8 rows x 2 clusters x 1024 multiply-adds, 2 x 1024 dequantizations
    assert serve.call_flops(1024, 2, 8) == 32768 + 2048


def test_mix_bytes_by_hand():
    from types import SimpleNamespace

    from bench.drivers import train

    rig = SimpleNamespace(n=2, x=1000, channel=None,
                          comm=SimpleNamespace(block=256))
    assert train.mix_bytes(rig) == 2 * 1000 * 4 + 2 * 1000 * 4
    rig.channel = object()
    # int8: 4 blocks of 256 quanta and 4 fp32 scales per row, 2 rows
    assert train.mix_bytes(rig) == 2 * (1024 + 16) + 2 * 1000 * 4


# ------------------------------------------------------ correctness check


def _vals(gen):
    out = {}
    for _seed, kind, vals in gen:
        out[kind] = vals
    return out


def _fails(vals, limits):
    return any(v > limits[k] for k, v in vals.items())


@pytest.mark.parametrize("workload", ["whisper_base.train_fp32",
                                      "mamba2_370m.train_int8ef"])
def test_train_run_passes_and_control_and_faults_fail(workload):
    out = run_small(workload, seconds=0.5)
    assert out["correct"], out["check"]
    assert out["attempted"] >= 1 and out["failed"] == 0
    cell = small_cell(workload)
    limits = cell.limits["limits"]
    # a seed on which the two clients select the same cluster, so the
    # exchange moves both rows
    got = _vals(cell.driver.readings(
        cell, [12], faults=("unchanged", "half_batch", "no_exchange")))
    assert not _fails(got["program"], limits), got["program"]
    for kind in ("control", "unchanged", "half_batch", "no_exchange"):
        assert _fails(got[kind], limits), (kind, got[kind])


def test_train_fault_turns_a_whole_run_incorrect():
    from types import SimpleNamespace

    from bench import faults

    cell = small_cell("whisper_base.train_fp32")

    def hook(real):
        return faults.train_step("unchanged", SimpleNamespace(step=real))

    out = run_small("whisper_base.train_fp32", seconds=0.5, cell=cell,
                    test_hooks={"step": hook})
    assert not out["correct"], out["check"]


def test_serve_run_passes_and_control_and_faults_fail():
    out = run_small("whisper_base.serve_int4_overload", seconds=1.0)
    assert out["correct"], out["check"]
    assert out["failed"] == 0
    assert set(out["metrics"]) == {"serve_users_per_s", "setup_s"}
    cell = small_cell("whisper_base.serve_int4_overload")
    limits = cell.limits["limits"]
    got = _vals(cell.driver.readings(cell, [11],
                                     faults=("half_batch", "altered")))
    assert not _fails(got["program"], limits), got["program"]
    for kind in ("control", "half_batch", "altered"):
        assert _fails(got[kind], limits), (kind, got[kind])


def test_serve_loop_stops_sending_at_the_close():
    """Offered far above what the server does, every call but the first
    carries a full batch, and nothing is sent once the window has closed:
    the rate reads the server's capacity, not the offered load."""
    import time

    import jax.numpy as jnp
    import numpy as np

    from bench.drivers import serve

    n, seconds = 2400, 0.5
    due = np.linspace(0.0, 1.0, n)
    mix = np.ones((n, 2), np.float32)
    checked = np.zeros(n, bool)
    checked[[3, 40]] = True

    def call(u):
        time.sleep(0.01)
        return jnp.asarray(u)

    done, calls, fps = serve.serve_loop(
        call, due, mix, seconds, 8, checked, lambda out: out,
        lambda _n: __import__("contextlib").nullcontext())
    sent = np.isfinite(done)
    assert 10 < calls < 50
    assert sent.sum() == 1 + 8 * (calls - 1)     # the first call finds one
    assert np.all(done[sent] < seconds + 0.05)
    assert sorted(fps) == [3, 40]


def test_serve_fault_turns_a_whole_run_incorrect():
    from bench import faults

    out = run_small(
        "whisper_base.serve_int4_overload", seconds=1.0, test_hooks={
            "call": lambda server: faults.serve_call("altered", server)})
    assert not out["correct"], out["check"]


# ---------------------------------------------------------------- harness


def test_no_tpu_no_result():
    with pytest.raises(core.NoDevice):
        core.check_device(1)
    with pytest.raises(core.NoDevice):
        core.peaks_for("cpu")


def test_benchmark_alone_exits_without_a_result(tmp_path):
    shutil.copy(core.ROOT / "BENCHMARK.json", tmp_path)
    shutil.copytree(core.BENCH, tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    spec = json.loads((tmp_path / "BENCHMARK.json").read_text())
    cell = spec["workloads"][0]["name"]
    res = subprocess.run(
        [sys.executable, "bench/run.py", "--workload", cell, "--seed", "1",
         "--seconds", "1", "--trace", "0"], cwd=tmp_path,
        capture_output=True, text=True, timeout=300,
        env={"PATH": "/usr/bin:/bin", "JAX_PLATFORMS": "cpu",
             "HOME": str(tmp_path)})
    assert res.returncode != 0
    assert res.stdout.strip() == ""


def test_every_cell_names_files_that_exist():
    spec = core.load_json(core.ROOT / "BENCHMARK.json")
    for w in spec["workloads"]:
        cell = core.find_cell(w["name"])
        assert cell.per_layer and cell.end_to_end
        assert any(m["name"] == "setup_s" for m in cell.end_to_end)
        for m in cell.per_layer:
            assert (core.BENCH / "metrics" / f"{m['name']}.py").exists()
