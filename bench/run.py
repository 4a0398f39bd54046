"""Run one benchmark cell once and print the result line.

    python3 bench/run.py --workload whisper_base.train_fp32 --seed 7 \\
        --seconds 10 --trace 0

The cell, its configuration, traffic, metrics and check limits are found
by name from ``BENCHMARK.json`` (see ``bench/core.py``). The last line of
standard output is one JSON object with ``correct``, ``attempted``,
``failed``, ``metrics``, ``device`` (and with ``--trace 1``,
``breakdown``), and ``check``: each number the correctness check
compared, beside its limit. The same numbers are the last lines of
standard error. Without a TPU, or with fewer chips than the cell asks
for, it exits non-zero and prints no result.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import pathlib  # noqa: E402
import sys  # noqa: E402

ROOT = pathlib.Path(__file__).resolve().parents[1]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]

from bench import core  # noqa: E402


def run_cell(cell: core.Cell, *, seed: int, seconds: float, trace: bool,
             device: dict, peaks: dict, t_start: float,
             compiles: core.CompileLog, test_hooks=None) -> dict:
    """Drive the cell once on the devices given; returns the result
    line's object. Tests call this with a small cell on the CPU."""
    import jax

    ctx = core.Ctx(cell=cell, seed=seed, seconds=seconds, trace=trace,
                   t_start=t_start, compiles=compiles,
                   devices=jax.devices()[:cell.chips],
                   test_hooks=test_hooks or {})
    outcome = cell.driver.run(ctx)
    parts = None
    if trace:
        parts = core.read_per_layer(cell, outcome, peaks, ctx.trace_dir)
    return core.result_line(outcome, cell, device, trace_parts=parts)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    core.use_cache_dir()
    cell = core.find_cell(args.workload)
    core.configure_cache()
    compiles = core.CompileLog().install()
    device = core.check_device(cell.chips)
    peaks = core.peaks_for(device["kind"])
    out = run_cell(cell, seed=args.seed, seconds=args.seconds,
                   trace=bool(args.trace), device=device, peaks=peaks,
                   t_start=T_START, compiles=compiles)
    core.print_checks([core.Check(k, v["value"], v["limit"])
                       for k, v in out["check"].items()])
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
