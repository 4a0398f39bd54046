"""The harness: find a cell by name, set up the device, run its driver,
read its metrics and print the result line.

Everything that belongs to one configuration, traffic mix or per-layer
metric lives in a file of its own, found by the name that
``BENCHMARK.json`` gives it:

- configuration ``<c>``: ``configs/<c>.json`` (the sizes as run, named by
  the cell's ``file``) and ``configs/<c>.py`` beside it (the program's
  model built from those sizes, the plain reference, the FLOP count);
- traffic ``<t>``: ``traffic/<t>.json``, whose ``driver`` key names the
  generator in ``drivers/`` that reads it;
- per-layer metric ``<m>``: ``metrics/<m>.py`` with ``read(ctx)``;
- limits of the correctness check of cell ``<w>``: ``limits/<w>.json``.
"""
from __future__ import annotations

import contextlib
import dataclasses
import importlib.util
import json
import math
import os
import pathlib
import sys
import time

BENCH = pathlib.Path(__file__).resolve().parent
ROOT = BENCH.parent
CACHE_DIR = ROOT / ".bench_cache" / "jax"
OUT_DIR = ROOT / ".bench_out"
COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"


class NoDevice(SystemExit):
    """No accelerator, or fewer chips than the cell asks for."""


def load_json(path):
    with open(path) as f:
        return json.load(f)


def load_module(path):
    """Import a file of the benchmark by its path (metric files carry dots
    in their names)."""
    path = pathlib.Path(path)
    mod_name = "bench_" + path.stem.replace(".", "_").replace("-", "_")
    spec = importlib.util.spec_from_file_location(mod_name, path)
    mod = importlib.util.module_from_spec(spec)
    sys.modules[mod_name] = mod
    spec.loader.exec_module(mod)
    return mod


@dataclasses.dataclass
class Cell:
    name: str
    chips: int
    config: dict        # the BENCHMARK.json configs entry
    sizes: dict         # the configuration file
    model: object       # configs/<c>.py
    traffic_name: str
    traffic: dict       # traffic/<t>.json
    driver: object      # drivers/<driver>.py
    limits: dict        # limits/<w>.json: {"limits": {number: limit}, ...}
    end_to_end: list    # BENCHMARK.json metric entries this cell reports
    per_layer: list


def _listed(metric: dict, cell: str) -> bool:
    return "workloads" not in metric or cell in metric["workloads"]


def find_cell(workload: str, spec: dict | None = None) -> Cell:
    """The cell named ``workload`` in ``BENCHMARK.json`` (or in ``spec``,
    an object of the same form)."""
    spec = spec or load_json(ROOT / "BENCHMARK.json")
    cells = {w["name"]: w for w in spec["workloads"]}
    if workload not in cells:
        raise SystemExit(f"unknown workload {workload!r}; have "
                         f"{sorted(cells)}")
    w = cells[workload]
    cfg = {c["name"]: c for c in spec["configs"]}[w["config"]]
    cfg_file = ROOT / cfg["file"]
    traffic = load_json(BENCH / "traffic" / f"{w['traffic']}.json")
    e2e = [m for m in spec["end_to_end"] if _listed(m, workload)]
    names = {m["name"] for m in e2e}
    # a per-layer metric without a workloads list goes with every cell
    # that reports the end-to-end metric it moves
    per_layer = [m for m in spec["per_layer"]
                 if (workload in m["workloads"] if "workloads" in m
                     else m["moves"] in names)]
    return Cell(
        name=workload, chips=int(w["chips"]), config=cfg,
        sizes=load_json(cfg_file),
        model=load_module(cfg_file.with_suffix(".py")),
        traffic_name=w["traffic"], traffic=traffic,
        driver=load_module(BENCH / "drivers" / f"{traffic['driver']}.py"),
        limits=load_json(BENCH / "limits" / f"{workload}.json"),
        end_to_end=e2e, per_layer=per_layer,
    )


def use_cache_dir() -> None:
    """JAX's persistent compilation cache at a fixed path inside the
    checkout, for this process and the program it drives. Set before JAX
    is imported; the program's own ``enable_compile_cache`` takes the
    directory from the same variable. The TPU runtime's own logs, which
    go to a fixed path under /tmp by default, are turned off unless the
    caller placed them."""
    os.environ["JAX_COMPILATION_CACHE_DIR"] = str(CACHE_DIR)
    os.environ.setdefault("TPU_LOG_DIR", "disabled")


def configure_cache() -> None:
    """Cache every program, however small or large: a run that finds one
    missing compiles it inside its set-up. The size cap some machines set
    in the environment would drop the round step's executable."""
    import jax

    jax.config.update("jax_compilation_cache_dir", str(CACHE_DIR))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    jax.config.update("jax_compilation_cache_max_size", -1)


def check_device(chips: int) -> dict:
    """The chips this cell runs on, or NoDevice: never the CPU."""
    import jax

    devs = jax.devices()
    if devs[0].platform != "tpu":
        raise NoDevice(f"bench: no TPU found (platform "
                       f"{devs[0].platform!r}); the benchmark runs only on "
                       "a TPU")
    if len(devs) < chips:
        raise NoDevice(f"bench: the cell needs {chips} chips, found "
                       f"{len(devs)}")
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": chips}


def peaks_for(kind: str) -> dict:
    table = load_json(BENCH / "peaks.json")["devices"]
    if kind not in table:
        raise NoDevice(f"bench: device kind {kind!r} is not in peaks.json "
                       f"(have {sorted(table)})")
    return table[kind]


def seed_words(seed: int, n: int = 2):
    """``n`` uint32 words from any whole number (the driver's seeds need
    more than 32 bits)."""
    import numpy as np

    return np.random.SeedSequence(int(seed) % 2**64).generate_state(n)


def prng_key(seed: int):
    """A raw threefry key (uint32[2]) from any whole number."""
    import jax.numpy as jnp

    return jnp.asarray(seed_words(seed), dtype=jnp.uint32)


def memory_peak_bytes(devices) -> int:
    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use", 0)
             for d in devices]
    return int(max(peaks))


class CompileLog:
    """Counts backend compilations (count, seconds) in this process."""

    def __init__(self):
        self.count = 0
        self.seconds = 0.0

    def __call__(self, event, duration, **_):
        if event == COMPILE_EVENT:
            self.count += 1
            self.seconds += duration

    def install(self):
        import jax

        jax.monitoring.register_event_duration_secs_listener(self)
        return self


@dataclasses.dataclass
class Check:
    """One number compared by the correctness check, with its limit."""

    name: str
    value: float
    limit: float

    @property
    def ok(self) -> bool:
        return math.isfinite(self.value) and self.value <= self.limit


@dataclasses.dataclass
class Ctx:
    """What a driver gets: the cell, the run's arguments, the devices, the
    compile log, and the hook that traces the window."""

    cell: Cell
    seed: int
    seconds: float
    trace: bool
    t_start: float
    compiles: CompileLog
    devices: list = None
    trace_dir: pathlib.Path = None
    test_hooks: dict = dataclasses.field(default_factory=dict)

    def log(self, msg: str) -> None:
        print(f"[{time.perf_counter() - self.t_start:8.2f}s] {msg}",
              file=sys.stderr, flush=True)

    @contextlib.contextmanager
    def traced(self):
        """Profile the enclosed block when ``--trace 1``, into a fixed
        directory of the checkout; the driver writes its ``bench/window``
        span inside it."""
        if not self.trace:
            yield
            return
        import jax

        self.trace_dir = OUT_DIR / "trace" / self.cell.name
        if self.trace_dir.exists():
            import shutil

            shutil.rmtree(self.trace_dir)
        self.trace_dir.mkdir(parents=True)
        jax.profiler.start_trace(str(self.trace_dir))
        try:
            yield
        finally:
            jax.profiler.stop_trace()


def span(name: str):
    """A host span on the profiler's timeline (a no-op cost when no trace
    is being taken)."""
    import jax

    return jax.profiler.TraceAnnotation(name)


@dataclasses.dataclass
class Outcome:
    """What a driver returns."""

    attempted: int
    failed: int
    end_to_end: dict                 # metric name -> value
    counters: dict                   # what the per-layer readers need
    checks: list                     # [Check]
    memory_peak_bytes: int


@dataclasses.dataclass
class ReadCtx:
    """What a per-layer metric's ``read`` gets."""

    cell: Cell
    counters: dict
    peaks: dict
    chips: int
    trace: object        # bench.trace.Trace
    window: tuple        # (start_ns, end_ns) of the bench/window span

    def log(self, msg: str) -> None:
        """Why a reader reads nothing, on standard error."""
        print(msg, file=sys.stderr, flush=True)


def read_per_layer(cell: Cell, outcome: Outcome, peaks: dict, trace_dir):
    """Reduce the trace; run each per-layer reader that applies. Returns
    (metrics, device additions, breakdown)."""
    from bench.trace import Trace

    tr = Trace.load(Trace.find(trace_dir))
    w0, w1 = tr.window()
    devs = tr.devices[:cell.chips]
    if not devs:
        raise RuntimeError("the trace holds no device operations")
    busy = sum(tr.busy_ns(d, w0, w1) for d in devs) / len(devs) / 1e9
    rc = ReadCtx(cell=cell, counters=outcome.counters, peaks=peaks,
                 chips=cell.chips, trace=tr, window=(w0, w1))
    metrics = {}
    for m in cell.per_layer:
        reader = load_module(BENCH / "metrics" / f"{m['name']}.py")
        value = reader.read(rc)
        if value is not None:
            metrics[m["name"]] = {"value": float(value), "unit": m["unit"]}
    breakdown = {"device_ops": tr.top_ops(devs[0], w0, w1),
                 "idle_gaps": tr.idle_gaps(devs[0], w0, w1)}
    return metrics, {"busy_s": busy, "window_s": (w1 - w0) / 1e9}, breakdown


def result_line(outcome: Outcome, cell: Cell, device: dict, *,
                trace_parts=None) -> dict:
    checks = outcome.checks
    correct = bool(checks) and all(c.ok for c in checks)
    dev = dict(device, memory_peak_bytes=int(outcome.memory_peak_bytes))
    out = {"correct": correct, "attempted": int(outcome.attempted),
           "failed": int(outcome.failed)}
    if trace_parts is None:
        units = {m["name"]: m["unit"] for m in cell.end_to_end}
        out["metrics"] = {n: {"value": float(outcome.end_to_end[n]),
                              "unit": units[n]}
                          for n in units if n in outcome.end_to_end}
    else:
        metrics, extra, breakdown = trace_parts
        out["metrics"] = metrics
        dev.update(extra)
        out["breakdown"] = breakdown
    out["device"] = dev
    out["check"] = {c.name: {"value": c.value, "limit": c.limit}
                    for c in checks}
    return out


def print_checks(checks) -> None:
    for c in checks:
        print(f"check {c.name} {c.value!r} limit {c.limit!r} "
              f"{'ok' if c.ok else 'FAIL'}", file=sys.stderr, flush=True)
