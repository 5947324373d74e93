"""Reduce a JAX profiler trace of the measured window to numbers.

``read_xplane`` reads the ``.xplane.pb`` that ``jax.profiler`` writes with
``jax.profiler.ProfileData`` and hands its events to ``reduce_events``;
the reduction works on plain tuples only, so a test can give it a small
synthetic trace.

Host events are ``(line, name, start_ns, duration_ns)``; device events are
``(plane, line, name, start_ns, duration_ns)``. A device plane is one whose
name starts with ``/device:`` and is not a ``CUSTOM`` one: its ``XLA Ops``
line holds every operation the device ran, nested ones included, and its
``XLA Modules`` line every program execution (a launch). The window is the
host span named ``WINDOW_SPAN`` that the harness opens around the timed
call, on the line of the Python thread that runs the program (its name
is the interpreter's: ``python``, ``python3``). An idle gap is charged
to the innermost span of that line (the JAX calls the program made:
dispatches, transfers, waits) that covers the gap's middle, or to
``HOST_PYTHON`` where none does.
"""
from __future__ import annotations

import array
import collections
import pathlib

import numpy as np

WINDOW_SPAN = "chipbench.window"
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
HOST_PYTHON = "host python between JAX calls"
# ops that only contain other ops: their time is their children's
CONTAINERS = ("%while", "%conditional", "%call")
# ops that move data between chips, with their -start / -done halves
COLLECTIVES = ("all-to-all", "all-gather", "all-reduce", "reduce-scatter",
               "collective-permute")
TOP_N = 10
NAME_CHARS = 100


def is_device_plane(name: str) -> bool:
    return name.startswith("/device:") and not name.startswith(
        "/device:CUSTOM")


def find_xplane(trace_dir) -> pathlib.Path | None:
    files = sorted(pathlib.Path(trace_dir).rglob("*.xplane.pb"))
    return files[-1] if files else None


def read_xplane(path) -> dict | None:
    import jax
    pd = jax.profiler.ProfileData.from_file(str(path))
    host = []
    for plane in pd.planes:
        if not is_device_plane(plane.name):
            host += [(line.name, e.name, e.start_ns, e.duration_ns)
                     for line in plane.lines for e in line.events]

    def device():
        for plane in pd.planes:
            if is_device_plane(plane.name):
                for line in plane.lines:
                    if line.name in (OPS_LINE, MODULES_LINE):
                        for e in line.events:
                            yield (plane.name, line.name, e.name,
                                   e.start_ns, e.duration_ns)

    return reduce_events(host, device())


def busy_and_gaps(starts, ends, w0: float, w1: float):
    """Union of [start, end) intervals clipped to [w0, w1): -> (busy
    length, list of idle gaps (start, end) inside the window)."""
    s = np.clip(np.asarray(starts, np.float64), w0, w1)
    e = np.clip(np.asarray(ends, np.float64), w0, w1)
    keep = e > s
    s, e = s[keep], e[keep]
    if not s.size:
        return 0.0, [(w0, w1)]
    order = np.argsort(s, kind="stable")
    s, e = s[order], e[order]
    reach = np.maximum.accumulate(e)
    new = np.ones(s.size, bool)
    new[1:] = s[1:] > reach[:-1]
    first = np.flatnonzero(new)
    last = np.append(first[1:] - 1, s.size - 1)
    us, ue = s[first], reach[last]
    busy = float(np.sum(ue - us))
    edges = np.concatenate([[w0], np.column_stack([us, ue]).ravel(), [w1]])
    gaps = [(a, b) for a, b in zip(edges[0::2], edges[1::2]) if b > a]
    return busy, gaps


def label_gaps(spans, gaps) -> collections.Counter:
    """Idle time per innermost covering span name. ``spans`` are the
    (start, end, name) of one host thread, so they nest properly: a sweep
    in time order with a stack of open spans finds each gap's innermost."""
    idle = collections.Counter()
    spans = sorted(spans, key=lambda x: (x[0], -x[1]))
    stack: list = []
    i = 0
    for a, b in sorted(gaps):
        t = 0.5 * (a + b)
        while i < len(spans) and spans[i][0] <= t:
            while stack and stack[-1][1] <= spans[i][0]:
                stack.pop()
            stack.append(spans[i])
            i += 1
        while stack and stack[-1][1] <= t:
            stack.pop()
        idle[stack[-1][2] if stack else HOST_PYTHON] += b - a
    return idle


def is_collective(name: str) -> bool:
    return name.lstrip("%").startswith(COLLECTIVES)


def reduce_events(host, device) -> dict | None:
    """-> readings of one traced window, or None when the trace holds no
    window span or no device operation inside it. ``busy_s`` and
    ``collective_s`` are per chip, averaged over the chips: the union of
    the window's op intervals, and of those of its collectives."""
    win = [(ln, s, s + d) for ln, n, s, d in host if n == WINDOW_SPAN]
    if not win:
        return None
    host_line, w0, w1 = win[0]
    starts, ends = {}, {}
    coll = collections.defaultdict(lambda: (array.array("d"),
                                            array.array("d")))
    launches = collections.Counter()
    ops = collections.Counter()
    for plane, line, name, s, d in device:
        if not is_device_plane(plane):
            continue
        if line == MODULES_LINE:
            launches[plane] += w0 <= s < w1
            continue
        starts.setdefault(plane, array.array("d")).append(s)
        ends.setdefault(plane, array.array("d")).append(s + d)
        if is_collective(name):
            coll[plane][0].append(s)
            coll[plane][1].append(s + d)
        lo, hi = max(s, w0), min(s + d, w1)
        if hi > lo and not name.startswith(CONTAINERS):
            ops[name[:NAME_CHARS]] += hi - lo
    planes = sorted(set(starts) | set(launches))
    busy, collective, gaps = 0.0, 0.0, []
    for plane in planes:
        b, g = busy_and_gaps(starts.get(plane, []), ends.get(plane, []),
                             w0, w1)
        busy += b
        gaps += g
        if plane in coll:
            collective += busy_and_gaps(*coll[plane], w0, w1)[0]
    if not planes or busy <= 0:
        return None
    idle = label_gaps([(s, s + d, n) for ln, n, s, d in host
                       if ln == host_line and n != WINDOW_SPAN and d > 0],
                      gaps)
    n = len(planes)
    return {
        "window_s": (w1 - w0) * 1e-9,
        "busy_s": busy / n * 1e-9,
        "collective_s": collective / n * 1e-9,
        "launches": sum(launches.values()) / n,
        "chips": n,
        "device_ops": [[k, v * 1e-9] for k, v in ops.most_common(TOP_N)],
        "idle_gaps": [[k, v / n * 1e-9] for k, v in idle.most_common(TOP_N)],
    }
