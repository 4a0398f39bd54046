"""Readings that the correctness check's limits are set from.

    python3 bench/control.py --workload whisper_base.train_fp32 \\
        --seeds 1 2 3 ... [--faults [name ...]]

For each seed, in one process on the chip at the cell's own size: the
program's first rounds against the plain reference (the lower readings),
and the control, which is the reference itself holding its parameters in
bfloat16, against the reference (the upper readings). With ``--faults``
it also reads each planted fault of ``bench/faults.py``. One JSON line per
seed and kind; the benchmark's own runs never run this.
"""
from __future__ import annotations

import argparse
import json
import pathlib
import sys
import time

ROOT = pathlib.Path(__file__).resolve().parents[1]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]

from bench import core  # noqa: E402


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--faults", nargs="*", default=None,
                    help="plant these faults of bench/faults.py too "
                         "(none named: every fault of the cell's driver)")
    args = ap.parse_args(argv)
    core.use_cache_dir()
    cell = core.find_cell(args.workload)
    core.configure_cache()
    core.check_device(cell.chips)
    from bench import faults as fault_mod

    t0 = time.perf_counter()
    faults = (() if args.faults is None
              else args.faults or fault_mod.FAULTS[cell.traffic["driver"]])
    for seed, kind, vals in cell.driver.readings(cell, args.seeds, faults):
        print(json.dumps({"seed": seed, "kind": kind, **vals,
                          "t": round(time.perf_counter() - t0, 1)}),
              flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
