"""Reduction of one profiler trace (``.xplane.pb``) to device numbers.

Device planes are those named ``/device:TPU:<n>``. On each, the line
``XLA Ops`` holds one event per operation that ran and ``XLA Modules``
one per program execution. From them:

- busy: the union of the operation intervals inside the window,
  averaged over the chips;
- per program: executions and summed device seconds. On the chip the
  engine's programs all read ``jit__unknown(<fingerprint>)`` (they jit
  ``functools.partial`` objects), so each execution is named by the
  host's dispatch of it: the n-th ``PjitFunction(<name>)`` span of the
  host is the n-th program execution of a single device, aligned by the
  offset at which the programs that do carry a name agree;
- per operation: executions and summed self seconds (an operation's
  time less the operations nested in it, as a ``while`` holds its
  body's), keyed by the HLO text the trace names it by;
- idle gaps: the spaces between busy intervals, each named by the
  harness's host span (``step``, ``submit``, ``wait for arrival``) and
  the innermost host span that cover its middle.

The window is the host span named ``window`` when the trace has one,
else the extent of all device events.
"""
from __future__ import annotations

import glob
import os
import re
from collections import defaultdict
from typing import Dict, List, Optional, Sequence, Tuple

DEVICE_PLANE = re.compile(r"^/device:TPU:\d+$")
DISPATCH = re.compile(r"^PjitFunction\((.*)\)$")
HARNESS_SPANS = ("step", "submit", "wait for arrival")
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
WINDOW_SPAN = "window"
TOP = 10

Interval = Tuple[float, float]


def find_xplane(directory: str) -> str:
    paths = sorted(glob.glob(os.path.join(directory, "**", "*.xplane.pb"),
                             recursive=True))
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {directory}")
    return paths[-1]


def program_name(name: str) -> str:
    """``jit__decode_step(123)`` -> ``_decode_step``."""
    return re.sub(r"^jit_", "", re.sub(r"\(-?\d+\)$", "", name))


def op_label(name: str) -> str:
    """A short name for an operation whose trace name is its HLO text:
    ``%fusion.94 = f32[8,151936]{...} fusion(...)`` -> ``%fusion.94
    fusion f32[8,151936]``."""
    if " = " not in name:
        return name[:80]
    lhs, rhs = name.split(" = ", 1)
    kind = re.search(r" ([a-z][a-z-]*)\(", rhs)
    shape = re.match(r"\(?([a-z0-9]+\[[0-9,]*\])", rhs)
    return " ".join(x for x in (lhs, kind and kind.group(1),
                                shape and shape.group(1)) if x)


def label_programs(modules, dispatches) -> List[str]:
    """A name for each module execution (sorted by start), from the
    host dispatch sequence (sorted by start)."""
    names = [program_name(n) for n, _, _ in modules]
    best, best_hits = 0, -1
    for off in range(-8, 9):
        hits = sum(1 for i, n in enumerate(names)
                   if n != "_unknown" and 0 <= i + off < len(dispatches)
                   and dispatches[i + off] == n)
        if hits > best_hits:
            best, best_hits = off, hits
    return [dispatches[i + best] if n == "_unknown"
            and 0 <= i + best < len(dispatches) else n
            for i, n in enumerate(names)]


def self_times(events):
    """(name, start, self duration) of properly nested events."""
    out, stack = [], []
    for name, t, dur in sorted(events, key=lambda e: (e[1], -e[2])):
        while stack and t >= stack[-1][1] + stack[-1][2]:
            stack.pop()
        if stack:
            stack[-1][3] -= dur
        rec = [name, t, dur, dur]
        out.append(rec)
        stack.append(rec)
    return [(n, t, s) for n, t, _, s in out]


def _outermost(spans):
    """(start, name) of the spans not nested in an earlier one."""
    out, end = [], float("-inf")
    for a, b, name in sorted(spans, key=lambda x: (x[0], -x[1])):
        if a >= end:
            out.append((a, name))
            end = b
    return out


def _merge(iv: List[Interval]) -> List[Interval]:
    out: List[Interval] = []
    for a, b in sorted(iv):
        if out and a <= out[-1][1]:
            if b > out[-1][1]:
                out[-1] = (out[-1][0], b)
        else:
            out.append((a, b))
    return out


def _clip(iv: Sequence[Interval], lo: float, hi: float) -> List[Interval]:
    return [(max(a, lo), min(b, hi)) for a, b in iv if b > lo and a < hi]


def _events(line):
    for e in line.events:
        yield e.name, float(e.start_ns), float(e.duration_ns)


def reduce_profile(pd) -> Dict:
    """Reduce a ``jax.profiler.ProfileData``; times in seconds."""
    host_spans: List[Tuple[float, float, str]] = []
    dispatch_lines: List[List[Tuple[float, str]]] = []
    devices: Dict[str, Dict[str, list]] = {}
    for plane in pd.planes:
        if DEVICE_PLANE.match(plane.name):
            d = devices.setdefault(plane.name, {"ops": [], "modules": []})
            for line in plane.lines:
                if line.name == OPS_LINE:
                    d["ops"].extend(_events(line))
                elif line.name == MODULES_LINE:
                    d["modules"].extend(_events(line))
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                disp = []
                for name, t, dur in _events(line):
                    host_spans.append((t, t + dur, name))
                    m = DISPATCH.match(name)
                    if m:
                        disp.append((t, t + dur, m.group(1)))
                dispatch_lines.append(_outermost(disp))
    if not devices:
        raise ValueError("trace holds no /device:TPU plane")
    # one host thread records the dispatches (another may echo them)
    dispatches = [n for _, n in max(dispatch_lines, key=len, default=[])]
    win = [(a, b) for a, b, n in host_spans if n == WINDOW_SPAN]
    if win:
        lo, hi = win[0]
    else:
        ends = [(t, t + dur) for d in devices.values()
                for _, t, dur in d["ops"]]
        lo, hi = min(a for a, _ in ends), max(b for _, b in ends)
    window_s = (hi - lo) * 1e-9

    busy, gaps = [], []
    programs: Dict[str, List[float]] = defaultdict(lambda: [0, 0.0])
    ops: Dict[str, List[float]] = defaultdict(lambda: [0, 0.0])
    for dname, d in sorted(devices.items()):
        ivs = _merge(_clip([(t, t + dur) for _, t, dur in d["ops"]],
                           lo, hi))
        busy.append(sum(b - a for a, b in ivs) * 1e-9)
        mods = sorted(d["modules"], key=lambda e: e[1])
        for (name, t, dur), label in zip(mods, label_programs(
                mods, dispatches)):
            if lo <= t < hi:
                p = programs[label]
                p[0] += 1
                p[1] += dur * 1e-9
        for name, t, dur in self_times(d["ops"]):
            if lo <= t < hi:
                o = ops[name]
                o[0] += 1
                o[1] += dur * 1e-9
        if dname.endswith(":0"):
            edges = [lo] + [x for iv in ivs for x in iv] + [hi]
            gaps = [(edges[i], edges[i + 1])
                    for i in range(0, len(edges), 2)
                    if edges[i + 1] > edges[i]]
    spans = [s for s in host_spans if s[2] != WINDOW_SPAN]
    named = []
    for a, b in sorted(gaps, key=lambda g: g[0] - g[1])[:TOP]:
        named.append([_host_label(spans, (a + b) / 2), (b - a) * 1e-9])
    n = len(devices)
    short: Dict[str, float] = defaultdict(float)
    for k, v in ops.items():
        short[op_label(k)] += v[1] / n
    return {
        "chips": n,
        "window_s": window_s,
        "busy_s": sum(busy) / n,
        "programs": {k: v for k, v in programs.items()},
        "ops": {k: v for k, v in ops.items()},
        "device_ops": [[k, v] for k, v in sorted(
            short.items(), key=lambda kv: -kv[1])[:TOP]],
        "idle_gaps": named,
    }


def _host_label(spans, t: float) -> str:
    """``<harness span>: <innermost span>`` covering time ``t``."""
    inner: Optional[Tuple[float, str]] = None
    outer: Optional[Tuple[float, str]] = None
    for a, b, name in spans:
        if a <= t <= b:
            if inner is None or b - a < inner[0]:
                inner = (b - a, name)
            if name in HARNESS_SPANS and (outer is None
                                          or b - a < outer[0]):
                outer = (b - a, name)
    parts = [x[1] for x in (outer, inner) if x is not None]
    if len(parts) == 2 and parts[0] == parts[1]:
        parts = parts[:1]
    return ": ".join(parts) if parts else "no host span"


def reduce_file(path: str) -> Dict:
    from jax.profiler import ProfileData
    if os.path.isdir(path):
        path = find_xplane(path)
    return reduce_profile(ProfileData.from_file(path))
