"""Reduce a profiler trace (``.xplane.pb``) to the numbers the metrics read.

The harness wraps the traced part of a run's window in a host span named
``chipbench.window``.  Within that span, for every device plane:

* ``busy_s`` -- the union of the intervals in which the device ran a
  program.  On a TPU these are the events of the "XLA Modules" line: one
  per program execution, and never dropped;
* ``ops`` -- the self time of each operation (its duration less that of
  the operations nested in it, such as a scan's body in its loop), from the
  "XLA Ops" line.  The profiler keeps a bounded buffer of these, so in a
  long window they may stop before its end; ``ops_covered_s`` says how far
  they reach;
* ``loop_s`` -- the union of the intervals of the "XLA Ops" line's
  ``while`` operations: the time the device spent inside loops, such as a
  scan over slots, its body included and the work before and after it
  left out.  ``ops_complete`` says whether that line reaches as far as
  the last program, so that nothing of it was dropped;
* ``gaps`` -- the idle intervals, each labelled by what the host was doing:
  the innermost host event that covers the gap's midpoint.  Gaps shorter
  than ``SHORT_GAP_NS`` (the device's own pauses between programs) are
  summed under one label instead.

``reduce`` averages these over the device planes the trace holds.
Nothing here knows about a particular cell.
"""
from __future__ import annotations

import glob
import os
import re
from collections import defaultdict

import numpy as np

WINDOW = "chipbench.window"

# On a TPU each chip is a plane "/device:TPU:<n>".
TPU_PLANE = r"/device:TPU:\d+$"
TPU_BUSY = r"XLA Modules$"
TPU_OPS = r"XLA Ops$"
HOST_PLANE = r"/host:CPU$"
SHORT_GAP_NS = 10_000
SHORT_GAP = "gaps under 10 us between device programs"


def find_xplane(log_dir: str) -> str:
    """The newest ``.xplane.pb`` the profiler wrote under ``log_dir``."""
    paths = glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"),
                      recursive=True)
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {log_dir}")
    return max(paths, key=os.path.getmtime)


def union(starts: np.ndarray, ends: np.ndarray):
    """Merge intervals; return the disjoint ``(starts, ends)``, sorted."""
    if starts.size == 0:
        return starts, ends
    order = np.argsort(starts, kind="stable")
    s, e = starts[order], ends[order]
    reach = np.maximum.accumulate(e)
    new = np.ones(s.size, bool)
    new[1:] = s[1:] > reach[:-1]
    first = np.flatnonzero(new)
    last = np.append(first[1:] - 1, s.size - 1)
    return s[first], reach[last]


def self_times(starts, ends, names) -> dict:
    """Time of each name, less the time of the events nested in it."""
    order = sorted(range(len(names)), key=lambda i: (starts[i], -ends[i]))
    out = defaultdict(float)
    stack: list = []  # open events: (end, name)
    for i in order:
        s, e = starts[i], ends[i]
        while stack and stack[-1][0] <= s:
            stack.pop()
        if stack:
            out[stack[-1][1]] -= e - s
        out[names[i]] += e - s
        stack.append((e, names[i]))
    return out


_HLO = re.compile(r"^(%[\w.\-]+) = .*? ([a-z][\w\-]*)\(")


def op_label(name: str) -> str:
    """``%fusion.171 fusion`` for an HLO instruction's full text."""
    m = _HLO.match(name)
    return f"{m.group(1)} {m.group(2)}" if m else name[:80]


def is_loop(name: str) -> bool:
    """Whether an operation's event is a ``while`` operation: named by its
    full HLO text (a TPU's "XLA Ops" line) or by its name alone
    (``while.5``, as the CPU client records it)."""
    m = _HLO.match(name)
    if m:
        return m.group(2) == "while"
    return re.fullmatch(r"while(\.\d+)?", name) is not None


def _events(line):
    starts, durs, names = [], [], []
    for ev in line.events:
        starts.append(ev.start_ns)
        durs.append(ev.duration_ns)
        names.append(ev.name)
    s = np.asarray(starts, np.float64)
    return s, s + np.asarray(durs, np.float64), names


def _merge(parts):
    if not parts:
        return np.zeros(0), np.zeros(0), []
    return (np.concatenate([p[0] for p in parts]),
            np.concatenate([p[1] for p in parts]),
            [n for p in parts for n in p[2]])


def _label(host, mid: float) -> str:
    """Innermost host event covering ``mid`` (``idle`` when none does)."""
    s, e, names = host
    cover = np.flatnonzero((s <= mid) & (e >= mid))
    if cover.size == 0:
        return "idle"
    return names[cover[np.argmin(e[cover] - s[cover])]]


def reduce(path: str, *, device_plane: str = TPU_PLANE,
           busy_line: str = TPU_BUSY, ops_line: str = TPU_OPS,
           host_plane: str = HOST_PLANE, window: str = WINDOW,
           top: int = 10) -> dict:
    """Busy time, top operations and labelled idle gaps within the window.

    Returns ``window_s``, ``devices`` (planes found), ``busy_s`` (mean over
    them), ``busy_by_device``, ``ops_covered_s`` (mean time from the
    window's start to its last recorded operation), ``ops_complete``
    (every device's operations reach within ``SHORT_GAP_NS`` of the end of
    its last program), ``loop_s`` (mean time inside ``while``
    operations), and ``device_ops``
    and ``idle_gaps``: each a list of ``[name, seconds]`` per device, at
    most ``top`` long.
    """
    from jax.profiler import ProfileData

    data = ProfileData.from_file(path)
    host_parts = []
    busy_parts, ops_parts = defaultdict(list), defaultdict(list)
    for plane in data.planes:
        if re.search(device_plane, plane.name):
            for line in plane.lines:
                if re.search(busy_line, line.name):
                    busy_parts[plane.name].append(_events(line))
                if re.search(ops_line, line.name):
                    ops_parts[plane.name].append(_events(line))
        if re.search(host_plane, plane.name):
            host_parts.extend(_events(line) for line in plane.lines)
    hs, he, hn = _merge(host_parts)
    win = [i for i, n in enumerate(hn) if n == window]
    if not win:
        raise ValueError(f"no host span {window!r} in {path}")
    w0, w1 = float(hs[win[0]]), float(he[win[0]])
    keep = np.asarray([n != window for n in hn], bool)
    host = (hs[keep], he[keep], [n for n, k in zip(hn, keep) if k])

    busy, covered, busy_end, loops = {}, {}, {}, {}
    ops = defaultdict(float)
    gaps = defaultdict(float)
    for plane_name, parts in busy_parts.items():
        s, e, _ = _merge(parts)
        s, e = np.clip(s, w0, w1), np.clip(e, w0, w1)
        us, ue = union(s[e > s], e[e > s])
        busy[plane_name] = float(np.sum(ue - us))
        busy_end[plane_name] = float(ue[-1] - w0) if ue.size else 0.0
        g0 = np.concatenate([[w0], ue])
        g1 = np.concatenate([us, [w1]])
        short = (g1 - g0) < SHORT_GAP_NS
        gaps[SHORT_GAP] += float(np.sum(np.maximum(g1 - g0, 0)[short]))
        for a, b in zip(g0[~short], g1[~short]):
            gaps[_label(host, (a + b) / 2)] += b - a
    for plane_name, parts in ops_parts.items():
        s, e, names = _merge(parts)
        s, e = np.clip(s, w0, w1), np.clip(e, w0, w1)
        live = np.flatnonzero(e > s)
        covered[plane_name] = float(e[live].max() - w0) if live.size else 0.0
        loop = [i for i in live if is_loop(names[i])]
        ls, le = union(s[loop], e[loop])
        loops[plane_name] = float(np.sum(le - ls))
        for name, ns in self_times(s[live].tolist(), e[live].tolist(),
                                   [names[i] for i in live]).items():
            ops[op_label(name)] += ns
    n_dev = max(len(busy), 1)

    def ranked(table):
        rows = sorted(table.items(), key=lambda kv: -kv[1])[:top]
        return [[name, ns / n_dev / 1e9] for name, ns in rows]

    return {
        "window_s": (w1 - w0) / 1e9,
        "devices": sorted(busy),
        "busy_s": sum(busy.values()) / n_dev / 1e9,
        "busy_by_device": {k: v / 1e9 for k, v in sorted(busy.items())},
        "ops_covered_s": sum(covered.values()) / n_dev / 1e9,
        "ops_complete": bool(busy) and all(
            covered.get(k, 0.0) >= end - SHORT_GAP_NS
            for k, end in busy_end.items()
        ),
        "loop_s": sum(loops.values()) / n_dev / 1e9,
        "device_ops": ranked(ops),
        "idle_gaps": ranked(gaps),
    }
