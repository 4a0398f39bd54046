"""The named-scope path and the program of each device op in a profiler
trace, and the program's own host spans.

``bench/trace.py`` reduces a trace through ``jax.profiler.ProfileData``,
which gives each event's own stats but not those of its metadata. On a
TPU, each op's metadata carries ``tf_op``, the op's named-scope path
(``jit(step_stream_packed)/fedspd/local_step/vmap(jvp(attn))/dot_general:``),
and ``program_id``, the id of the compiled program the op belongs to.
This module decodes just the planes' ``event_metadata`` and
``stat_metadata`` from the XSpace protobuf with a small wire-format
decoder, skipping every line wholesale. It then joins each op to them on
(program id, instruction name). An op's program is the ``XLA Modules``
event that covers it in time. Instruction names are unique inside a
program.

    sc = Scopes.load(path)                   # or scopes.of(ctx), in a reader
    progs = sc.programs(dev, ROUND_PROGRAM)  # the round step's program ids
    sc.ops_ns(dev, lambda o: o.program in progs
              and under(o.scope, "fedspd/local_step"), w0, w1)
    sc.idle_gaps(dev, w0, w1)   # [["bench/dispatch>serve/block", s], ...]
"""
from __future__ import annotations

import bisect
import dataclasses
import functools
import os
import re
import statistics

from bench import trace as tr

# Module names of the programs the readers look inside: the jitted round
# step (core/fedspd.make_round_step's four variants) and the server's
# personalized entry point (serve/server.ClusterPlaneServer).
ROUND_PROGRAM = re.compile(r"jit_step_(stream|full)(_packed)?")
SERVE_PROGRAM = re.compile(r"jit__personalized_impl")
# The exchange's Pallas kernel takes the round step's name (as
# metrics/mix_hbm_roofline.py matches it); it sits under none of the
# round step's phase scopes.
EXCHANGE_KERNEL = re.compile(r"step_(stream|full)(_packed)?")
ROUND_PHASES = ("fedspd/select", "fedspd/cluster", "fedspd/local_step",
                "fedspd/scatter", "fedspd/consensus")
# Host spans the program writes itself (serve/server.py, the experiment
# loop's repro/round), as opposed to the harness's bench/* spans.
PROGRAM_SPANS = ("serve/", "repro/")
MODULES_LINE = "XLA Modules"
_MODULE = re.compile(r"^(.*)\((\d+)\)$")
_WRAPPED = re.compile(r"\w+\((.*)\)")

# field numbers of tsl/profiler/protobuf/xplane.proto
_SPACE_PLANES = 1
_PLANE_NAME, _PLANE_EVENT_MD, _PLANE_STAT_MD = 2, 4, 5
_MD_ID, _MD_NAME, _MD_STATS = 1, 2, 5
_STAT_MD_ID, _STAT_UINT, _STAT_INT, _STAT_STR = 1, 3, 4, 5


def _varint(b, i: int) -> tuple:
    out = shift = 0
    while True:
        c = b[i]
        i += 1
        out |= (c & 0x7F) << shift
        shift += 7
        if c < 0x80:
            return out, i


def _fields(b):
    """(field number, value) of each field of one protobuf message;
    length-delimited values are memoryviews, nothing is copied."""
    i, n = 0, len(b)
    while i < n:
        key, i = _varint(b, i)
        field, wire = key >> 3, key & 7
        if wire == 0:
            v, i = _varint(b, i)
        elif wire == 1:
            v, i = b[i:i + 8], i + 8
        elif wire == 2:
            size, i = _varint(b, i)
            v, i = b[i:i + size], i + size
        elif wire == 5:
            v, i = b[i:i + 4], i + 4
        else:
            raise ValueError(f"protobuf wire type {wire} is not supported")
        yield field, v


def _map_values(entry):
    """The value of one map<int64, message> entry."""
    for field, v in _fields(entry):
        if field == 2:
            return v
    return b""


def _int(v) -> int:
    """A stat's integer: uint64 and int64 values both arrive as varints;
    a negative int64 comes as its two's complement."""
    return v - (1 << 64) if v >= 1 << 63 else v


def read_metadata(path) -> dict:
    """{device plane name: {(program id, instruction name): tf_op}} of
    the ops whose metadata carries both stats."""
    with open(path, "rb") as f:
        data = memoryview(f.read())
    out = {}
    for field, plane in _fields(data):
        if field != _SPACE_PLANES:
            continue
        name, event_md, stat_md = "", [], {}
        for pf, v in _fields(plane):
            if pf == _PLANE_NAME:
                name = bytes(v).decode()
            elif pf == _PLANE_EVENT_MD:
                event_md.append(v)
            elif pf == _PLANE_STAT_MD:
                md = dict(_fields(_map_values(v)))
                stat_md[md.get(_MD_ID)] = bytes(md.get(_MD_NAME, b"")).decode()
        if not name.startswith(tr.DEVICE_PREFIX):
            continue
        want = {k for k, v in stat_md.items() if v in ("tf_op", "program_id")}
        ops = {}
        for entry in event_md:
            op_name, stats = "", {}
            for mf, v in _fields(_map_values(entry)):
                if mf == _MD_NAME:
                    op_name = bytes(v).decode(errors="replace")
                elif mf == _MD_STATS:
                    st = dict(_fields(v))
                    if st.get(_STAT_MD_ID) not in want:
                        continue
                    key = stat_md[st[_STAT_MD_ID]]
                    if _STAT_STR in st:
                        stats[key] = bytes(st[_STAT_STR]).decode(
                            errors="replace")
                    elif _STAT_UINT in st:
                        stats[key] = st[_STAT_UINT]
                    else:
                        stats[key] = _int(st.get(_STAT_INT, 0))
            if "tf_op" in stats and "program_id" in stats:
                ops[(stats["program_id"], tr._short(op_name))] = stats["tf_op"]
        out[name] = ops
    return out


def scope_path(tf_op: str) -> str:
    """``tf_op`` is ``<scope path>:<op type>``; keep the path."""
    return tf_op.rsplit(":", 1)[0] if ":" in tf_op else tf_op


@functools.lru_cache(maxsize=None)
def _joined(scope: str) -> str:
    return "/" + "/".join(segments(scope)) + "/"


def segments(scope: str) -> list:
    """A scope path's segments with transform wrappers taken off:
    ``jit(f)/a/vmap(jvp(attn))/dot_general`` -> ``[f, a, attn,
    dot_general]``."""
    out = []
    for seg in scope.split("/"):
        while True:
            m = _WRAPPED.fullmatch(seg)
            if not m:
                break
            seg = m.group(1)
        out.append(seg)
    return out


def under(scope: str, name: str) -> bool:
    """True when ``scope`` runs through the scope ``name`` (one segment,
    or several joined by ``/``), at any depth."""
    return f"/{name}/" in _joined(scope)


@dataclasses.dataclass(frozen=True)
class ScopedOp:
    """One device op with its scope path (``""`` when its metadata has
    none), the id of the program it ran in (None outside any), and
    whether it is a Pallas kernel."""

    start: int
    end: int
    name: str
    scope: str
    program: int | None
    pallas: bool = False


@dataclasses.dataclass
class Scopes:
    ops: dict       # device name -> [ScopedOp], sorted by start
    modules: dict   # device name -> [(start, end, program id, module name)]
    spans: list     # host spans: [(start, end, name)], sorted by start
    logged: set = dataclasses.field(default_factory=set)  # splits logged

    @classmethod
    def load(cls, path, trace: tr.Trace | None = None) -> "Scopes":
        """Join the metadata to the ops of ``trace`` (loaded from ``path``
        when not given)."""
        from jax.profiler import ProfileData

        trace = trace or tr.Trace.load(path)
        meta = read_metadata(path)
        modules = {}
        for plane in ProfileData.from_file(str(path)).planes:
            if not plane.name.startswith(tr.DEVICE_PREFIX):
                continue
            mods = []
            for line in plane.lines:
                if line.name != MODULES_LINE:
                    continue
                for ev in line.events:
                    m = _MODULE.match(ev.name)
                    if m:
                        s = int(ev.start_ns)
                        mods.append((s, s + int(ev.duration_ns),
                                     int(m.group(2)), m.group(1)))
            modules[plane.name] = sorted(mods)
        ops = {}
        for dev, dev_ops in trace.ops.items():
            mods = modules.get(dev, [])
            starts = [m[0] for m in mods]
            names = meta.get(dev, {})
            out = []
            for o in dev_ops:
                k = bisect.bisect_right(starts, o.start) - 1
                prog = (mods[k][2] if k >= 0 and o.start < mods[k][1]
                        else None)
                tf_op = names.get((prog, o.name), "")
                out.append(ScopedOp(o.start, o.end, o.name,
                                    scope_path(tf_op), prog, o.is_pallas))
            ops[dev] = out
        return cls(ops=ops, modules=modules, spans=list(trace.spans))

    @property
    def devices(self) -> list:
        return sorted(d for d, o in self.ops.items() if o)

    def programs(self, dev: str, module, w0: int = None,
                 w1: int = None) -> set:
        """Ids of the programs whose module name ``module`` (a compiled
        pattern) matches whole, run on the device (inside the window when
        one is given)."""
        return {p for s, e, p, name in self.modules.get(dev, [])
                if module.fullmatch(name)
                and (w0 is None or (e > w0 and s < w1))}

    def ops_ns(self, dev: str, pred, w0: int, w1: int) -> int:
        """Union of the intervals of the device's ops ``pred`` accepts,
        inside the window."""
        return tr.total(tr.clip(tr.merge(
            (o.start, o.end) for o in self.ops[dev] if pred(o)), w0, w1))

    def scope_ns(self, dev: str, pred, w0: int, w1: int) -> int:
        """Time of the device's ops whose scope path ``pred`` accepts,
        inside the window (their union, so nested events count once)."""
        return self.ops_ns(dev, lambda o: pred(o.scope), w0, w1)

    def span_ns(self, name: str, w0: int, w1: int) -> list:
        """Durations of the host spans named ``name`` that start inside
        the window."""
        return [e - s for s, e, n in self.spans if n == name and w0 <= s < w1]

    def idle_gaps(self, dev: str, w0: int, w1: int, k: int = 10) -> list:
        """[(name, seconds)] for the longest stretches in which the device
        ran nothing. Each is named by the harness span (``bench/*``) that
        covered most of it, then, after ``>``, by the innermost of the
        program's own spans that covered most of it, where one did."""
        busy = tr.merge((o.start, o.end) for o in self.ops[dev])
        gaps = tr.subtract([(w0, w1)], tr.clip(busy, w0, w1))
        gaps = sorted(gaps, key=lambda g: g[0] - g[1])[:k]
        harness = [sp for sp in self.spans if sp[2].startswith("bench/")
                   and sp[2] != tr.WINDOW_SPAN]
        program = [sp for sp in self.spans
                   if sp[2].startswith(PROGRAM_SPANS)]
        out = []
        for g0, g1 in gaps:
            name = _cover(harness, g0, g1) or "none"
            inner = _innermost(program, g0, g1)
            out.append([f"{name}>{inner}" if inner else name,
                        (g1 - g0) / 1e9])
        return out


def _cover(spans, g0: int, g1: int):
    """Name of the span covering most of [g0, g1)."""
    best, cover = None, 0
    for s, e, n in spans:
        c = min(e, g1) - max(s, g0)
        if c > cover:
            best, cover = n, c
    return best


def _innermost(spans, g0: int, g1: int):
    """Name of the shortest span covering at least half of [g0, g1), or,
    where none does, of the span covering most of it."""
    half = [(e - s, n) for s, e, n in spans
            if 2 * (min(e, g1) - max(s, g0)) >= g1 - g0]
    return min(half)[1] if half else _cover(spans, g0, g1)


# ------------------------------------------------------------ for readers

_CACHE = {}


def trace_path(ctx):
    """The traced run's ``.xplane.pb``: the harness writes it under a
    fixed directory per cell (``core.Ctx.traced``); a test may give its
    own as ``ctx.trace_path``."""
    given = getattr(ctx, "trace_path", None)
    if given:
        return str(given)
    from bench import core

    return tr.Trace.find(core.OUT_DIR / "trace" / ctx.cell.name)


def of(ctx):
    """The Scopes of the run the reader reads, decoded once per trace
    file and shared by the readers; None, said on standard error, when
    there is no file."""
    try:
        path = trace_path(ctx)
    except FileNotFoundError as e:
        ctx.log(f"scopes: {e}")
        return None
    key = (path, os.path.getmtime(path))
    if key not in _CACHE:
        _CACHE.clear()
        _CACHE[key] = Scopes.load(path, trace=ctx.trace)
    return _CACHE[key]


def per_run_ms(ctx, metric: str, module, scope_pred, what: str,
               counter: str):
    """Device ms per round (or call) of the ops of the programs named
    ``module`` whose scope ``scope_pred`` accepts, in the window, over the
    counter ``counter``, averaged over the cell's chips. None, said on
    standard error, when no such op ran."""
    n = ctx.counters.get(counter)
    sc = of(ctx)
    if not n or sc is None:
        return None
    w0, w1 = ctx.window
    devs = sc.devices[:ctx.chips]
    ns = 0
    for d in devs:
        progs = sc.programs(d, module, w0, w1)
        ns += sc.ops_ns(d, lambda o: o.program in progs
                         and scope_pred(o.scope), w0, w1)
    if ns <= 0:
        ctx.log(f"{metric}: not read: no op of the {module.pattern} "
                f"program under {what} ran in the window")
        return None
    return ns / len(devs) / n / 1e6


def round_ms(ctx, metric: str, *names: str):
    """Device ms per round of the round step's ops under any of the scopes
    ``names``; logs, once per trace, the round's split."""
    log_split(ctx, "round", ROUND_PROGRAM, round_part, "rounds")
    return per_run_ms(ctx, metric, ROUND_PROGRAM,
                      lambda s: any(under(s, n) for n in names),
                      " or ".join(names), "rounds")


def is_exchange(op: ScopedOp) -> bool:
    """The exchange's Pallas kernel, by the instruction name the round
    step gives it."""
    return op.pallas and EXCHANGE_KERNEL.fullmatch(
        re.sub(r"\.\d+$", "", op.name.lstrip("%"))) is not None


def round_part(op: ScopedOp):
    """The round step's phase an op belongs to, the exchange kernel, or
    None."""
    if is_exchange(op):
        return "exchange kernel"
    return next((n for n in ROUND_PHASES if under(op.scope, n)), None)


def serve_part(op: ScopedOp):
    """``serve/<scope>`` of a serve program's op (``serve/mix_int4``,
    ``serve/unpack``), or None."""
    seg = segments(op.scope)
    k = seg.index("serve") if "serve" in seg else -1
    return f"serve/{seg[k + 1]}" if 0 <= k < len(seg) - 1 else None


def log_split(ctx, label: str, module, part, counter: str) -> None:
    """Log, once per trace and label, the device ms per ``counter`` of
    each part (``part(op)``) of the programs named ``module``, and the
    time under no part: an op with no part (a loop, a copy XLA added
    without metadata) counts only where no op with a part runs."""
    sc = of(ctx)
    n = ctx.counters.get(counter) or 0
    if sc is None or not n or label in sc.logged:
        return
    sc.logged.add(label)
    w0, w1 = ctx.window
    for d in sc.devices[:ctx.chips]:
        progs = sc.programs(d, module, w0, w1)
        mine = [o for o in sc.ops[d] if o.program in progs]
        busy = tr.clip(tr.merge((o.start, o.end) for o in mine), w0, w1)
        if not busy:
            continue
        by = {}
        for o in mine:
            key = part(o)
            if key is not None:
                by.setdefault(key, []).append((o.start, o.end))
        parts = {k: tr.total(tr.clip(tr.merge(v), w0, w1))
                 for k, v in by.items()}
        rest = tr.total(tr.subtract(busy, tr.merge(
            iv for v in by.values() for iv in v)))
        ms = lambda ns: f"{ns / n / 1e6:.3f}"  # noqa: E731
        split = ", ".join(f"{k} {ms(v)}" for k, v in sorted(parts.items()))
        ctx.log(f"{label} split on {d} (device ms per {counter[:-1]}, of "
                f"{ms(tr.total(busy))} busy): {split}; under none of "
                f"them: {ms(rest)} "
                f"({100.0 * rest / tr.total(busy):.2f}%)")


def median_span_ms(ctx, metric: str, name: str):
    """Median host ms of the spans named ``name`` in the window; None,
    said on standard error, when there is none."""
    sc = of(ctx)
    if sc is None:
        return None
    w0, w1 = ctx.window
    ns = sc.span_ns(name, w0, w1)
    if not ns:
        ctx.log(f"{metric}: not read: no {name} span in the window")
        return None
    return statistics.median(ns) / 1e6
