"""Reduce a JAX profiler trace (``.xplane.pb``) to what the metrics read.

A trace holds one plane per device (``/device:TPU:<i>``), whose
``XLA Ops`` line has one event per operation that ran there, and a host
plane (``/host:CPU``), whose per-thread lines carry the
``jax.profiler.TraceAnnotation`` spans the harness writes around its own
calls. All timestamps share one clock, so the window is read from the
harness's ``bench/window`` span and not from the host's clock.

    tr = Trace.load(path)
    w0, w1 = tr.window()                       # the bench/window span
    tr.busy_ns(dev, w0, w1)                    # union of op intervals
    tr.op_ns(dev, lambda op: ..., w0, w1)      # time of matching ops
    tr.kernel_ns(dev, lambda op: ..., w0, w1)  # time of matching Pallas
                                               # kernels, and the others
    tr.exposed_ns(dev, is_collective, w0, w1)  # collective time with no
                                               # other op running
"""
from __future__ import annotations

import dataclasses
import glob
import os
import re

WINDOW_SPAN = "bench/window"
DEVICE_PREFIX = "/device:"
HOST_PLANE = "/host:CPU"
OPS_LINE = "XLA Ops"
PALLAS_TARGET = 'custom_call_target="tpu_custom_call"'
_RESULT = re.compile(r"^%\S+ = (\w+)\[([\d,]*)\]")


@dataclasses.dataclass(frozen=True)
class Op:
    """One device operation: start and end in ns, its HLO instruction name
    (``%fusion.3``), and the event's whole text with every string stat the
    profiler attached (the instruction, its operands, custom-call target,
    module...): what matchers search."""

    start: int
    end: int
    name: str
    text: str

    def matches(self, needles) -> bool:
        return any(n in self.text for n in needles)

    @property
    def instruction(self) -> str:
        """The instruction's name without its ``%`` and numeric suffix:
        ``%mix_int4.1`` -> ``mix_int4``."""
        return re.sub(r"\.\d+$", "", self.name.lstrip("%"))

    @property
    def result(self) -> tuple:
        """(element type, element count) of the instruction's one array
        result, from its text (``%x.1 = f32[2,1024]{...} ...``); ("", 0)
        for a tuple or where the text carries no shape."""
        m = _RESULT.match(self.text)
        if not m:
            return "", 0
        n = 1
        for d in filter(None, m.group(2).split(",")):
            n *= int(d)
        return m.group(1), n

    @property
    def is_pallas(self) -> bool:
        return PALLAS_TARGET in self.text


def _short(name: str) -> str:
    """A TPU trace names an op by its whole HLO instruction; keep the
    instruction's own name."""
    return name.split(" = ", 1)[0]


def _stats_text(event) -> str:
    try:
        stats = dict(event.stats)
    except (TypeError, ValueError):
        return ""
    return " ".join(str(v) for v in stats.values() if isinstance(v, str))


def merge(intervals):
    """Sorted, disjoint union of (start, end) intervals."""
    out = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            if e > out[-1][1]:
                out[-1][1] = e
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def clip(intervals, w0: int, w1: int):
    return [(max(s, w0), min(e, w1)) for s, e in intervals
            if e > w0 and s < w1]


def total(intervals) -> int:
    return sum(e - s for s, e in intervals)


def subtract(a, b):
    """Parts of the disjoint sorted intervals ``a`` not covered by the
    disjoint sorted intervals ``b``."""
    out = []
    j = 0
    for s, e in a:
        cur = s
        while j < len(b) and b[j][1] <= cur:
            j += 1
        k = j
        while k < len(b) and b[k][0] < e:
            if b[k][0] > cur:
                out.append((cur, b[k][0]))
            cur = max(cur, b[k][1])
            k += 1
        if cur < e:
            out.append((cur, e))
    return out


@dataclasses.dataclass
class Trace:
    ops: dict     # device name -> [Op], sorted by start
    spans: list   # host spans: [(start, end, name)], sorted by start

    @staticmethod
    def find(directory) -> str:
        """The newest ``.xplane.pb`` under a profiler output directory."""
        files = glob.glob(os.path.join(str(directory), "**", "*.xplane.pb"),
                          recursive=True)
        if not files:
            raise FileNotFoundError(f"no .xplane.pb under {directory}")
        return max(files, key=os.path.getmtime)

    @classmethod
    def load(cls, path) -> "Trace":
        from jax.profiler import ProfileData

        data = ProfileData.from_file(str(path))
        ops, spans = {}, []
        for plane in data.planes:
            if plane.name.startswith(DEVICE_PREFIX):
                dev = []
                for line in plane.lines:
                    if line.name != OPS_LINE:
                        continue
                    for ev in line.events:
                        s = int(ev.start_ns)
                        dev.append(Op(s, s + int(ev.duration_ns),
                                      _short(ev.name),
                                      ev.name + " " + _stats_text(ev)))
                dev.sort(key=lambda o: o.start)
                ops[plane.name] = dev
            elif plane.name == HOST_PLANE:
                for line in plane.lines:
                    for ev in line.events:
                        s = int(ev.start_ns)
                        spans.append((s, s + int(ev.duration_ns), ev.name))
        spans.sort()
        return cls(ops=ops, spans=spans)

    @property
    def devices(self) -> list:
        return sorted(d for d, o in self.ops.items() if o)

    def window(self, name: str = WINDOW_SPAN) -> tuple:
        wins = [(s, e) for s, e, n in self.spans if n == name]
        if not wins:
            raise ValueError(f"trace has no {name!r} span")
        return wins[-1]

    def busy(self, dev: str, w0: int, w1: int):
        """Union of the device's op intervals inside the window."""
        return clip(merge((o.start, o.end) for o in self.ops[dev]), w0, w1)

    def busy_ns(self, dev: str, w0: int, w1: int) -> int:
        return total(self.busy(dev, w0, w1))

    def op_ns(self, dev: str, pred, w0: int, w1: int) -> int:
        """Time of the device's ops that ``pred`` accepts, inside the
        window (their union, so nested events count once)."""
        return total(clip(merge((o.start, o.end) for o in self.ops[dev]
                                if pred(o)), w0, w1))

    def kernel_ns(self, dev: str, accept, w0: int, w1: int) -> tuple:
        """(time of the Pallas kernels ``accept`` takes, inside the window;
        sorted names of the Pallas kernels in the window it does not
        take). A reader that times one kernel reads nothing while another
        kernel it cannot tell apart runs beside it."""
        others = sorted({o.name for o in self.ops[dev]
                         if o.is_pallas and not accept(o)
                         and o.end > w0 and o.start < w1})
        ns = self.op_ns(dev, lambda o: o.is_pallas and accept(o), w0, w1)
        return ns, others

    def exposed_ns(self, dev: str, pred, w0: int, w1: int) -> int:
        """Time of the ops ``pred`` accepts during which no other op runs
        on that device."""
        mine = clip(merge((o.start, o.end) for o in self.ops[dev]
                          if pred(o)), w0, w1)
        others = merge((o.start, o.end) for o in self.ops[dev]
                       if not pred(o))
        return total(subtract(mine, others))

    def top_ops(self, dev: str, w0: int, w1: int, k: int = 10) -> list:
        """[(name, seconds)] of the ops that took most time in the window,
        summed by name."""
        by = {}
        for o in self.ops[dev]:
            s, e = max(o.start, w0), min(o.end, w1)
            if e > s:
                by[o.name] = by.get(o.name, 0) + (e - s)
        top = sorted(by.items(), key=lambda kv: -kv[1])[:k]
        return [[n, t / 1e9] for n, t in top]

    def idle_gaps(self, dev: str, w0: int, w1: int, k: int = 10,
                  prefix: str = "bench/") -> list:
        """[(host span, seconds)] for the longest stretches in which the
        device ran nothing, each named by the harness span (``prefix``)
        that covered most of it: what the host was doing meanwhile."""
        gaps = subtract([(w0, w1)], self.busy(dev, w0, w1))
        gaps = sorted(gaps, key=lambda g: g[0] - g[1])[:k]
        mine = [(s, e, n) for s, e, n in self.spans
                if n.startswith(prefix) and n != WINDOW_SPAN]
        out = []
        for g0, g1 in gaps:
            best, cover = "none", 0
            for s, e, n in mine:
                c = min(e, g1) - max(s, g0)
                if c > cover:
                    best, cover = n, c
            out.append([best, (g1 - g0) / 1e9])
        return out
