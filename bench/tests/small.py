"""Cells cut to a size a CPU test run can hold: the same files, drivers and
checks, with the configuration's widths and the traffic's lengths made
small. Used by the tests under ``bench/tests``."""
from __future__ import annotations

import copy
import time

from bench import core

SMALL_RUN = {
    "whisper_base": {"n_layers": 2, "d_model": 128, "n_heads": 4,
                     "n_kv_heads": 4, "d_ff": 256, "vocab": 512,
                     "encoder_layers": 2, "encoder_frames": 64,
                     "encoder_d_model": 128},
    "mamba2_370m": {"n_layers": 2, "d_model": 128, "vocab": 512,
                    "ssm_state": 16, "ssm_headdim": 32},
}
SMALL_TRAFFIC = {
    "train": {"seq": 16, "token_vocab": 64, "docs_per_client": 8,
              "pool": 4},
    "serve": {"users": 1000, "rate_per_s": 40.0, "check_share": 0.25},
}


# The mamba2_370m cell's files are kept under bench/, but BENCHMARK.json
# does not list it: its first run in a checkout, which compiles, takes
# longer than a run may (PERF.md). The tests still run it from here.
UNLISTED = {
    "configs": [{"name": "mamba2_370m",
                 "source": "https://huggingface.co/state-spaces/mamba2-370m",
                 "file": "bench/configs/mamba2_370m.json",
                 "reduced": ["n_layer"], "why": "decoder-only SSD model"}],
    "workloads": [{"name": "mamba2_370m.train_int8ef",
                   "config": "mamba2_370m", "traffic": "train_int8ef",
                   "chips": 1, "why": "int8 exchange with error feedback"}],
}


def small_cell(workload: str) -> core.Cell:
    spec = core.load_json(core.ROOT / "BENCHMARK.json")
    for key in UNLISTED:
        spec[key] = spec[key] + UNLISTED[key]
    cell = core.find_cell(workload, spec=spec)
    cell.sizes = copy.deepcopy(cell.sizes)
    cell.sizes["run"].update(SMALL_RUN[cell.config["name"]])
    cell.traffic = dict(cell.traffic, **SMALL_TRAFFIC[cell.traffic["driver"]])
    return cell


def run_small(workload: str, *, seed: int = 11, seconds: float = 1.0,
              trace: bool = False, test_hooks=None, cell=None) -> dict:
    """One whole run of a small cell on the CPU, the device check
    skipped."""
    from bench import run

    cell = cell or small_cell(workload)
    device = {"platform": "cpu", "kind": "cpu", "count": cell.chips}
    peaks = core.peaks_for("TPU v5 lite")
    return run.run_cell(cell, seed=seed, seconds=seconds, trace=trace,
                        device=device, peaks=peaks,
                        t_start=time.perf_counter(),
                        compiles=core.CompileLog(), test_hooks=test_hooks)
