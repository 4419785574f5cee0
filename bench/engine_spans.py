"""The serving engine's own spans in a profiler trace, reduced by phase.

The engine enters each phase of a step as a profiler annotation named
``engine.<phase>`` (``profiler_span`` in ``serve/engine.py``): ``step``;
in it ``admission`` (holding the ``prefill`` dispatch), ``inputs`` (the
decode step's host arrays and their transfers), ``decode`` (the jitted
dispatch) and ``emit`` (tokens, retirement, the token callback);
``read`` around every blocking device-to-host read, wherever it
happens; ``spec_round``; the page pool's ``spill`` and ``fault``. The
spans are on the clock of the device's events, so the idle time of
device 0 inside the window (the gaps between its busy intervals, as
``devtrace`` takes them) can be laid under them.

For each span name, over the spans that start inside the window: the
count, the summed duration, the summed self time (duration less the
engine spans nested in it), and the idle time of device 0 during which
a span of that name was the innermost engine span. ``idle_by_span``
lists the ten largest of those, with the idle time under no engine span
as ``no engine span``.

The harness hands a reader the trace's reduction, not the trace:
:func:`for_record` finds the profile the traced run wrote (its
``bench-trace-*`` directory under the temporary directory is removed
only after the readers ran), checks that its window is the one the
record's reduction has, and keeps this reduction in the record for the
other readers. A trace of a program without these spans reduces to
none, and the readers then report nothing.
"""
from __future__ import annotations

import dataclasses
import glob
import os
import sys
import tempfile
from collections import defaultdict
from typing import Dict, Iterator, List, Optional, Tuple

import devtrace

PREFIX = "engine."
READ = "read"
NONE = "no engine span"
TRACE_DIRS = "bench-trace-*"    # harness.run's tempfile.mkdtemp prefix
KEY = "engine_spans"            # where for_record keeps the reduction


@dataclasses.dataclass
class Span:
    name: str                   # the phase, without PREFIX
    start: float                # ns
    dur: float                  # ns
    args: Dict
    children: List["Span"] = dataclasses.field(default_factory=list)

    @property
    def end(self) -> float:
        return self.start + self.dur

    def has(self, name: str) -> bool:
        return any(c.name == name for c in self.children)

    def below(self, name: str) -> float:
        """Summed ns of the outermost spans called ``name`` nested in
        this one."""
        return sum(c.dur if c.name == name else c.below(name)
                   for c in self.children)


def walk(spans: List[Span]) -> Iterator[Span]:
    for s in spans:
        yield s
        yield from walk(s.children)


def _trees(events) -> List[Span]:
    """Nest one host thread's engine spans; returns the outermost."""
    roots: List[Span] = []
    stack: List[Span] = []
    for s in sorted(events, key=lambda s: (s.start, -s.dur)):
        while stack and s.start >= stack[-1].end:
            stack.pop()
        (stack[-1].children if stack else roots).append(s)
        stack.append(s)
    return roots


def _innermost(spans: List[Span], out: List[Tuple[float, float, str]]):
    """Append (start, end, name) segments that partition the spans'
    intervals by their innermost span."""
    for s in spans:
        t = s.start
        for c in s.children:
            if c.start > t:
                out.append((t, c.start, s.name))
            t = max(t, c.end)
        if s.end > t:
            out.append((t, s.end, s.name))
        _innermost(s.children, out)


def _overlap(a: List[Tuple[float, float]], segs) -> Dict[str, float]:
    """ns of the sorted disjoint intervals ``a`` that lie under each
    name of the sorted disjoint segments ``segs``."""
    out: Dict[str, float] = defaultdict(float)
    i = 0
    for lo, hi, name in segs:
        while i < len(a) and a[i][1] <= lo:
            i += 1
        j = i
        while j < len(a) and a[j][0] < hi:
            out[name] += min(hi, a[j][1]) - max(lo, a[j][0])
            j += 1
    return out


def reduce_profile(pd) -> Optional[Dict]:
    """Reduce a ``jax.profiler.ProfileData``; None when it holds no
    engine span. Times in seconds."""
    lines: List[List[Span]] = []
    window = None
    ops, modules = [], []
    for plane in pd.planes:
        if plane.name.startswith("/host:"):
            for line in plane.lines:
                mine = []
                for e in line.events:
                    if e.name.startswith(PREFIX):
                        mine.append(Span(e.name[len(PREFIX):],
                                         float(e.start_ns),
                                         float(e.duration_ns),
                                         dict(e.stats)))
                    elif e.name == devtrace.WINDOW_SPAN and window is None:
                        window = (float(e.start_ns),
                                  float(e.start_ns) + float(e.duration_ns))
                if mine:
                    lines.append(_trees(mine))
        elif (devtrace.DEVICE_PLANE.match(plane.name)
              and plane.name.endswith(":0")):
            for line in plane.lines:
                if line.name == devtrace.OPS_LINE:
                    ops = [(t, t + d) for _, t, d in devtrace._events(line)]
                elif line.name == devtrace.MODULES_LINE:
                    modules = list(devtrace._events(line))
    if not lines or not ops:
        return None
    lo, hi = window if window else (min(a for a, _ in ops),
                                    max(b for _, b in ops))
    busy = devtrace._merge(devtrace._clip(ops, lo, hi))
    edges = [lo] + [x for iv in busy for x in iv] + [hi]
    idle = [(edges[i], edges[i + 1]) for i in range(0, len(edges), 2)
            if edges[i + 1] > edges[i]]

    roots = [s for tree in lines for s in tree if lo <= s.start < hi]
    by_name: Dict[str, Dict] = defaultdict(
        lambda: {"count": 0, "total_s": 0.0, "self_s": 0.0, "idle_s": 0.0})
    for s in walk(roots):
        b = by_name[s.name]
        b["count"] += 1
        b["total_s"] += s.dur * 1e-9
        b["self_s"] += (s.dur - sum(c.dur for c in s.children)) * 1e-9
    under: Dict[str, float] = defaultdict(float)
    covered = 0.0
    for tree in lines:
        segs: List[Tuple[float, float, str]] = []
        _innermost(tree, segs)
        segs = [(max(a, lo), min(b, hi), n) for a, b, n in sorted(segs)
                if b > lo and a < hi]
        for name, ns in _overlap(idle, segs).items():
            under[name] += ns
            covered += ns
    for name, ns in under.items():
        by_name[name]["idle_s"] += ns * 1e-9
    idle_s = sum(b - a for a, b in idle) * 1e-9
    covered_s = covered * 1e-9
    top = dict((k, v * 1e-9) for k, v in under.items())
    top[NONE] = idle_s - covered_s
    unnamed = sum(1 for name, t, _ in modules
                  if lo <= t < hi
                  and devtrace.program_name(name) == "_unknown")
    return {
        "window_s": (hi - lo) * 1e-9,
        "idle_s": idle_s,
        "covered_s": covered_s,
        "by_name": dict(by_name),
        "idle_by_span": [[k, v] for k, v in sorted(
            top.items(), key=lambda kv: -kv[1])[:devtrace.TOP]],
        "unnamed_modules": unnamed,
        "roots": roots,
    }


def reduce_file(path: str) -> Optional[Dict]:
    from jax.profiler import ProfileData
    if os.path.isdir(path):
        path = devtrace.find_xplane(path)
    return reduce_profile(ProfileData.from_file(path))


def _log(msg: str):
    print(f"[bench] {msg}", file=sys.stderr, flush=True)


def report(red: Dict):
    """The reduction's summary, on standard error."""
    share = red["covered_s"] / red["idle_s"] if red["idle_s"] else 1.0
    _log(f"engine spans: device-0 idle {red['idle_s']:.6f} s of "
         f"{red['window_s']:.6f} s, {100 * share:.2f}% of it under an "
         f"engine span; {red['unnamed_modules']} module executions "
         f"named jit__unknown")
    _log("engine spans: idle_by_span " + repr(red["idle_by_span"]))
    for name, b in sorted(red["by_name"].items(),
                          key=lambda kv: -kv[1]["total_s"]):
        _log(f"engine span {name}: count {b['count']}, total "
             f"{b['total_s']:.6f} s, self {b['self_s']:.6f} s, idle "
             f"{b['idle_s']:.6f} s")


def for_record(rec: Dict, tmp: Optional[str] = None) -> Optional[Dict]:
    """The engine-span reduction of the traced run whose record this
    is, made once and kept in the record; None when the trace is not
    found or holds no engine span."""
    if KEY not in rec:
        rec[KEY] = _find(rec, tmp or tempfile.gettempdir())
        if rec[KEY] is not None:
            report(rec[KEY])
    return rec[KEY]


def _find(rec: Dict, tmp: str) -> Optional[Dict]:
    want = rec.get("trace", {}).get("window_s")
    dirs = sorted(glob.glob(os.path.join(tmp, TRACE_DIRS)),
                  key=os.path.getmtime, reverse=True)
    for d in dirs:
        try:
            red = reduce_file(d)
        except Exception as e:      # noqa: BLE001 — another run's files
            _log(f"engine spans: {d} not read ({type(e).__name__}: {e})")
            continue
        if red is not None and red["window_s"] == want:
            return red
    _log(f"engine spans: none in a trace of this window ({len(dirs)} "
         f"trace directories under {tmp})")
    return None
