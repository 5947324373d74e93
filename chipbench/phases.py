#!/usr/bin/env python3
"""Where the program's own names put the time of one traced window.

    python3 chipbench/phases.py --workload paper-logreg.sync --seed 7 \
        --seconds 2

The program names its round's device stages with ``jax.named_scope`` and
its host phases with ``jax.profiler.TraceAnnotation``, one list each in
``repro.telemetry.profiler`` (``DEVICE_SCOPES``, ``HOST_SPANS``). This
sets a cell up as ``run.py`` does (the first chunk, then one chunk more),
traces a window of whole chunks of about ``--seconds``, and prints one
JSON line:

* ``scopes`` -- device op time per scope in the window, per chip.
  Containers are left out, as ``tracing.reduce_events`` leaves them out; a
  nested scope counts under every scope on its path. A TPU op event
  carries no name path, so each op is given the ``op_name`` of its
  instruction in the compiled chunk's text (``hlo_op_paths``), found
  through the ``XLA Modules`` event around it; a fusion carries its
  root's.
* ``spans`` -- ``[count, union seconds]`` per program span (names that
  start with ``PROGRAM_SPAN``) on the window's thread, and per family
  (``repro.engine.*``).
* ``idle_phases`` -- idle device time charged to the innermost program
  span that covers the gap's middle, or to ``tracing.HOST_PYTHON``.
* ``op_coverage`` -- per chip, the share of the time a program ran (the
  union of ``XLA Modules``) that some op covers (the union of ``XLA
  Ops``): below 1 where the trace lost op events.
* ``dispatch_lead_ms`` -- for each ``repro.engine.dispatch`` span, how
  long after its start the next launch of the chunk program starts: a
  lead of 0 or more in every chunk shows the host spans and the device
  events on one clock.

With these it prints the window's ``rounds``, ``rounds_per_s``,
``host_syncs`` and the accepted reduction's ``busy_s``, ``collective_s``
and ``window_s``. It reports no metric of ``BENCHMARK.json`` and checks
nothing against the reference. It needs an accelerator, as ``run.py``
does, and exits 1 without one.
"""
from __future__ import annotations

import argparse
import array
import bisect
import collections
import gc
import json
import math
import pathlib
import re
import sys
import time

BENCH_DIR = pathlib.Path(__file__).resolve().parent
ROOT = BENCH_DIR.parent
if str(BENCH_DIR) not in sys.path:
    sys.path.insert(0, str(BENCH_DIR))

import tracing  # noqa: E402

PROGRAM_SPAN = "repro."
DISPATCH_SPAN = "repro.engine.dispatch"
_PATH_WORD = re.compile(r"[A-Za-z0-9_]+")
_HLO_MODULE = re.compile(r"^HloModule\s+([^\s,]+)")
_EVENT_OP = re.compile(r"^%?([^\s=]+)")
_HLO_OP = re.compile(r'^\s*(?:ROOT\s+)?%?([^\s=]+)\s*=.*\bop_name="([^"]*)"')


def hlo_op_paths(hlo_text: str) -> dict:
    """``{(module, instruction): op_name}`` of compiled HLO text
    (``Compiled.as_text()``); a fusion instruction carries its root's
    ``op_name``."""
    out, module = {}, None
    for line in hlo_text.splitlines():
        m = _HLO_MODULE.match(line)
        if m:
            module = m.group(1)
            continue
        m = _HLO_OP.match(line)
        if m:
            out[(module, m.group(1))] = m.group(2)
    return out


def enclosing(mods, t: float) -> str | None:
    """The name of the interval of ``mods`` (sorted ``(start, end, name)``,
    disjoint, as one chip's programs are) that holds time ``t``."""
    i = bisect.bisect_right(mods, (t, math.inf)) - 1
    return mods[i][2] if i >= 0 and mods[i][0] <= t < mods[i][1] else None


def event_path(hlo_paths: dict, module: str | None, name: str) -> str | None:
    """The ``op_name`` of an op event: its instruction (the head of its
    name) in its program's compiled text."""
    m = _EVENT_OP.match(name)
    return hlo_paths.get((module, m.group(1))) if m else None


def path_scopes(path: str, scopes) -> set:
    """The scopes named on a path. JAX wraps a scope in the names of the
    transforms it went through (``transpose(jvp(ens))``), so the path is
    read as words."""
    return set(_PATH_WORD.findall(path)).intersection(scopes)


def module_name(event_name: str) -> str:
    """``jit_chunk(81)`` -> ``jit_chunk``, the ``HloModule`` name."""
    return event_name.split("(")[0]


def xplane_events(path, hlo_paths=None):
    """-> (host events, device events) of one ``.xplane.pb``, as
    ``tracing.read_xplane`` reads them, each device event with a sixth
    element: its op's name path from ``hlo_paths`` (``hlo_op_paths`` of
    the programs that ran), or None."""
    import jax
    pd = jax.profiler.ProfileData.from_file(str(path))
    host, device = [], []
    for plane in pd.planes:
        if not tracing.is_device_plane(plane.name):
            host += [(line.name, e.name, e.start_ns, e.duration_ns)
                     for line in plane.lines for e in line.events]
            continue
        lines = [ln for ln in plane.lines
                 if ln.name in (tracing.OPS_LINE, tracing.MODULES_LINE)]
        mods = sorted((e.start_ns, e.start_ns + e.duration_ns,
                       module_name(e.name))
                      for ln in lines if ln.name == tracing.MODULES_LINE
                      for e in ln.events)
        for ln in lines:
            for e in ln.events:
                p = None
                if hlo_paths and ln.name == tracing.OPS_LINE:
                    p = event_path(hlo_paths, enclosing(mods, e.start_ns),
                                   e.name)
                device.append((plane.name, ln.name, e.name, e.start_ns,
                               e.duration_ns, p))
    return host, device


def program_spans(prog, w0: float, w1: float) -> dict:
    """``{name: [count, union seconds]}`` of the program's spans
    ``(start, end, name)`` in the window, and the same for each family of
    names (``repro.engine.*``): the count of spans that start in the
    window, the union of their intervals clipped to it."""
    groups: dict = {}
    for s, e, nm in prog:
        for key in (nm, nm.rsplit(".", 1)[0] + ".*"):
            groups.setdefault(key, []).append((s, e))
    out = {}
    for key, iv in sorted(groups.items()):
        s, e = zip(*iv)
        union, _ = tracing.busy_and_gaps(s, e, w0, w1)
        count = sum(w0 <= a < w1 for a in s)
        if count or union > 0:
            out[key] = [count, union * 1e-9]
    return out


def op_coverage(op_s, op_e, mod_s, mod_e, w0: float, w1: float):
    """Share of one chip's program time in the window (union of modules)
    that ops cover: |ops & modules| / |modules|, None without a module."""
    mods, _ = tracing.busy_and_gaps(mod_s, mod_e, w0, w1)
    if mods <= 0:
        return None
    ops, _ = tracing.busy_and_gaps(op_s, op_e, w0, w1)
    both, _ = tracing.busy_and_gaps(list(op_s) + list(mod_s),
                                    list(op_e) + list(mod_e), w0, w1)
    return (ops + mods - both) / mods


def reduce_phases(host, device, scopes=()) -> dict | None:
    """-> ``scopes``, ``spans``, ``idle_phases`` and ``op_coverage`` of one
    traced window; None where ``tracing.reduce_events`` finds nothing to
    read (no window span, no device op in it). Device events may carry a
    sixth element, the op's name path."""
    win = [(ln, s, s + d) for ln, n, s, d in host
           if n == tracing.WINDOW_SPAN]
    if not win:
        return None
    host_line, w0, w1 = win[0]
    ops, mods = {}, {}
    scope_t = collections.Counter()
    for plane, line, name, s, d, *path in device:
        if not tracing.is_device_plane(plane):
            continue
        iv = mods if line == tracing.MODULES_LINE else ops
        starts, ends = iv.setdefault(plane, (array.array("d"),
                                             array.array("d")))
        starts.append(s)
        ends.append(s + d)
        lo, hi = max(s, w0), min(s + d, w1)
        if (line != tracing.MODULES_LINE and hi > lo and path and path[0]
                and not name.startswith(tracing.CONTAINERS)):
            for sc in path_scopes(path[0], scopes):
                scope_t[sc] += hi - lo
    planes = sorted(set(ops) | set(mods))
    none = (array.array("d"), array.array("d"))
    busy, gaps = 0.0, []
    for plane in planes:
        b, g = tracing.busy_and_gaps(*ops.get(plane, none), w0, w1)
        busy += b
        gaps += g
    if not planes or busy <= 0:
        return None
    n = len(planes)
    prog = [(s, s + d, nm) for ln, nm, s, d in host
            if ln == host_line and nm.startswith(PROGRAM_SPAN) and d > 0]
    phases = tracing.label_gaps(prog, gaps)
    return {
        "scopes": {k: v / n * 1e-9 for k, v in scope_t.most_common()},
        "spans": program_spans(prog, w0, w1),
        "idle_phases": [[k, v / n * 1e-9] for k, v in phases.most_common()],
        "op_coverage": [op_coverage(*ops.get(p, none), *mods.get(p, none),
                                    w0, w1) for p in planes],
    }


def dispatch_leads(host, device, module: str) -> list:
    """For each ``DISPATCH_SPAN`` in the window: ms from its start to the
    start of the next launch of ``module`` on the first chip (None where
    none follows)."""
    win = [(ln, s, s + d) for ln, n, s, d in host
           if n == tracing.WINDOW_SPAN]
    if not win:
        return []
    host_line, w0, w1 = win[0]
    planes = sorted({ev[0] for ev in device
                     if tracing.is_device_plane(ev[0])})
    starts = sorted(ev[3] for ev in device
                    if planes and ev[0] == planes[0]
                    and ev[1] == tracing.MODULES_LINE
                    and module_name(ev[2]) == module)
    leads = []
    for ln, nm, s, d in sorted(host, key=lambda h: h[2]):
        if ln == host_line and nm == DISPATCH_SPAN and w0 <= s < w1:
            i = bisect.bisect_left(starts, s)
            leads.append((starts[i] - s) * 1e-6 if i < len(starts)
                         else None)
    return leads


def window_events(cfg: dict, mix: dict, *, seed: int, seconds: float,
                  trace_dir) -> dict:
    """Set up the cell as ``harness.run_window`` does, trace a window of
    whole chunks of about ``seconds``, and read its trace. -> the
    window's ``rounds``, ``window_s`` and ``host_syncs`` (the blocking
    device-to-host reads the program counted in it), the chunk's
    ``module`` name and ``hlo_paths``, and the
    trace's ``host`` and ``device`` events (with op paths); the
    ``.xplane.pb`` is deleted once read."""
    import shutil

    import jax

    import harness
    from repro.sim import lower_rounds
    from repro.spec.build import RunHandle

    chunk = cfg["spec"]["engine"]["chunk"]
    spec, handle, _ = harness.first_chunk(cfg, mix, seed)

    def handle_for(rounds):
        return RunHandle(spec=spec.replace(**{"engine.rounds": rounds}),
                         sim=handle.sim, data=handle.data)

    # a handle's second run() compiles one program again: time the third
    handle_for(chunk).run()
    harness._block(handle.sim.state)
    t = time.perf_counter()
    handle_for(chunk).run()
    harness._block(handle.sim.state)
    n_chunks = max(1, round(seconds / (time.perf_counter() - t)))
    if "max_rounds" in cfg:
        n_chunks = max(1, min(n_chunks, (cfg["max_rounds"]
                                         - handle.sim.round_idx) // chunk))
    text = lower_rounds(handle.sim, chunk,
                        collect_w_tau=handle._w_stackable).compile().as_text()
    hlo_paths = hlo_op_paths(text)
    window = handle_for(n_chunks * chunk)
    gc.collect()
    shutil.rmtree(trace_dir, ignore_errors=True)
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 2
    jax.profiler.start_trace(str(trace_dir), profiler_options=opts)
    syncs0 = handle.sim.host_syncs
    try:
        t0 = time.perf_counter()
        with jax.profiler.TraceAnnotation(tracing.WINDOW_SPAN):
            summary = window.run()
            harness._block(handle.sim.state)
        window_s = time.perf_counter() - t0
    finally:
        jax.profiler.stop_trace()
    path = tracing.find_xplane(trace_dir)
    host, device = xplane_events(path, hlo_paths) if path else ([], [])
    if path:
        path.unlink()
    return {"rounds": summary["rounds"], "window_s": window_s,
            "host_syncs": handle.sim.host_syncs - syncs0,
            "module": _HLO_MODULE.match(text).group(1),
            "hlo_paths": hlo_paths, "host": host, "device": device}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, default=2.0)
    args = ap.parse_args(argv)
    sys.path[:0] = [str(ROOT / "src")]
    import harness
    import run
    from repro.telemetry.profiler import DEVICE_SCOPES
    manifest = harness.load_json(ROOT / "BENCHMARK.json")
    cell, _, cfg, mix = run.cell_files(manifest, args.workload)
    import jax
    if jax.devices()[0].platform == "cpu":
        return run.fail("needs an accelerator; JAX found only the CPU")
    ev = window_events(cfg, mix, seed=args.seed, seconds=args.seconds,
                       trace_dir=harness.TRACE_DIR
                       / f"phases-{args.workload}-{args.seed}")
    device5 = [d[:5] for d in ev["device"]]
    tr = tracing.reduce_events(ev["host"], device5) or {}
    out = {"workload": args.workload, "seed": args.seed,
           "rounds": ev["rounds"], "window_s": ev["window_s"],
           "rounds_per_s": ev["rounds"] / ev["window_s"],
           "host_syncs": ev["host_syncs"], "module": ev["module"],
           "hlo_ops": len(ev["hlo_paths"]),
           "busy_s": tr.get("busy_s"), "trace_window_s": tr.get("window_s"),
           "collective_s": tr.get("collective_s"),
           **(reduce_phases(ev["host"], ev["device"], DEVICE_SCOPES) or {}),
           "dispatch_lead_ms": dispatch_leads(ev["host"], device5,
                                              ev["module"])}
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
