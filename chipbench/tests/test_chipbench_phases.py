"""The program's names in a traced window (``phases.py``): scope time, span
unions, idle charged to program spans, op coverage, the dispatch lead, on
small synthetic traces and on a tiny run on the CPU."""
from __future__ import annotations

import pathlib
import sys

import pytest

sys.path.insert(0, str(pathlib.Path(__file__).resolve().parents[1]))

import phases  # noqa: E402
import tracing  # noqa: E402
from test_chipbench_trace import DEV, MS, trace  # noqa: E402

SCOPES = ("ens", "client_grad", "attention", "client_prox")


def named_trace():
    """``trace()`` with the program's names. Op name paths: the sort under
    ``ens``; fusion.3 under ``client_grad`` and, wrapped by transforms,
    ``attention``; fusion.4 with ``attention`` twice on its path; the while
    op (a container) and an op before the window under ``ens``. Program
    spans on the Python thread: a dispatch 40-60 ms, an objective read
    60-90 ms around the ``np.asarray`` wait, another 92-98 ms, bookkeeping
    20-30 ms (no gap there), one read after the window and one on
    another thread."""
    host, device = trace()
    host += [("python3", "repro.engine.dispatch", 40 * MS, 20 * MS),
             ("python3", "repro.run.objective", 60 * MS, 30 * MS),
             ("python3", "repro.run.objective", 92 * MS, 6 * MS),
             ("python3", "repro.engine.bookkeeping", 20 * MS, 10 * MS),
             ("python3", "repro.run.objective", 110 * MS, 5 * MS),
             ("main/1", "repro.engine.policy", 0, 100 * MS)]
    paths = {
        "%while.1": "jit(chunk)/while/body/ens",
        "%sort.2": "jit(chunk)/while/body/ens/sort",
        "%fusion.3": "jit(chunk)/while/body/client_grad/"
                     "transpose(jvp(attention))/dot_general",
        "%fusion.4": "jit(chunk)/while/body/client_grad/jvp(blocks)/"
                     "attention/remat(attention)/exp",
        "%early": "jit(chunk)/while/body/ens/add"}
    device = [ev + (paths.get(ev[2].split(" ")[0]),) for ev in device]
    return host, device


def test_scope_time_counts_each_scope_on_the_path_once():
    r = phases.reduce_phases(*named_trace(), SCOPES)
    assert r["scopes"] == {"ens": pytest.approx(0.010),
                           "client_grad": pytest.approx(0.020),
                           "attention": pytest.approx(0.020)}


def test_program_spans_count_and_union_in_the_window():
    spans = phases.reduce_phases(*named_trace(), SCOPES)["spans"]
    assert spans["repro.run.objective"] == [2, pytest.approx(0.036)]
    assert spans["repro.run.*"] == [2, pytest.approx(0.036)]
    assert spans["repro.engine.dispatch"] == [1, pytest.approx(0.020)]
    assert spans["repro.engine.*"] == [2, pytest.approx(0.030)]
    assert "repro.engine.policy" not in spans       # another thread


def test_idle_is_charged_to_the_innermost_program_span():
    host, device = named_trace()
    r = phases.reduce_phases(host, device, SCOPES)
    # a gap goes whole to the span around its middle: [50, 90) to the
    # objective read around the np.asarray wait (not to DevicePut),
    # [95, 100) to the second read, [0, 10) to no program span
    assert dict(r["idle_phases"]) == {
        "repro.run.objective": pytest.approx(0.045),
        tracing.HOST_PYTHON: pytest.approx(0.010)}
    # the accepted reduction of the same trace keeps its busy time and
    # still charges [50, 90) to the JAX call innermost there
    named = tracing.reduce_events(host, [d[:5] for d in device])
    plain = tracing.reduce_events(*trace())
    for key in ("window_s", "busy_s", "launches", "device_ops"):
        assert named[key] == plain[key]
    assert dict(named["idle_gaps"])["DevicePut"] == pytest.approx(0.040)


def test_op_coverage_reads_ops_missing_inside_a_module():
    host, device = trace()
    assert phases.reduce_phases(host, device)["op_coverage"] == \
        [pytest.approx(1.0)]
    # lose the ops of 40-50 ms inside jit_chunk's 10-50 ms: 35 of 45 ms
    lost = [ev for ev in device if not ev[2].startswith("%fusion.4")]
    assert phases.reduce_phases(host, lost)["op_coverage"] == \
        [pytest.approx(35 / 45)]


def test_a_trace_without_program_names_reads_nothing_new():
    host, device = trace()
    r = phases.reduce_phases(host, device, SCOPES)
    assert r["scopes"] == {} and r["spans"] == {}
    assert r["idle_phases"] == [[tracing.HOST_PYTHON, pytest.approx(0.055)]]
    assert phases.reduce_phases(host[1:], device) is None
    assert phases.reduce_phases(host, []) is None
    assert phases.dispatch_leads(host, device, "jit_chunk") == []


def test_the_dispatch_lead_is_read_on_one_clock():
    host, device = named_trace()
    host.append(("python3", "repro.engine.dispatch", 8 * MS, 1 * MS))
    # jit_chunk starts 2 ms after the dispatch at 8 ms; none follows the
    # dispatch at 40 ms (jit_lambda at 90 ms is another program)
    assert phases.dispatch_leads(host, device, "jit_chunk") == \
        [pytest.approx(2.0), None]
    assert phases.dispatch_leads(host, device, "jit_none") == [None, None]


HLO = """HloModule jit_chunk, entry_computation_layout={(f32[4]{0})->f32[4]{0}}

%fused_computation (param_0: f32[4]) -> f32[4] {
  %param_0 = f32[4]{0} parameter(0)
  ROOT %sine.0 = f32[4]{0} sine(%param_0), metadata={op_name="jit(chunk)/ens/sin"}
}

ENTRY %main.4 (x.1: f32[4]) -> f32[4] {
  %x.1 = f32[4]{0} parameter(0), metadata={op_name="x"}
  ROOT %sine_fusion = f32[4]{0} fusion(%x.1), kind=kLoop, calls=%fused_computation, metadata={op_name="jit(chunk)/ens/sin" source_file="a.py"}
}
"""


def test_op_path_from_the_hlo_metadata_of_its_program():
    paths = phases.hlo_op_paths(HLO)
    assert paths[("jit_chunk", "sine_fusion")] == "jit(chunk)/ens/sin"
    assert paths[("jit_chunk", "x.1")] == "x"
    # a TPU op event carries no path: its program is the module event
    # around it (``jit_chunk(81)``), its op the head of its name
    mods = [(0, 10, "jit_early"), (20, 50, "jit_chunk")]
    assert phases.enclosing(mods, 30) == "jit_chunk"
    assert phases.enclosing(mods, 15) is None
    assert phases.enclosing(mods, 50) is None
    name = "%sine_fusion = f32[4]{0:T(128)} fusion(f32[4]{0} %x.1)"
    assert phases.event_path(paths, "jit_chunk", name) == \
        "jit(chunk)/ens/sin"
    assert phases.event_path(paths, "jit_early", name) is None
    assert phases.event_path(paths, None, name) is None
    assert phases.path_scopes("jit(chunk)/transpose(jvp(ens))/x",
                              SCOPES) == {"ens"}
    assert phases.module_name("jit_chunk(81)") == "jit_chunk"


def test_hlo_metadata_of_a_compiled_program_names_its_scopes():
    import jax
    import jax.numpy as jnp

    def f(x):
        with jax.named_scope("ens"):
            y = jnp.sin(x) * 2.0
        return jnp.sum(y)

    text = jax.jit(jax.grad(f)).lower(jnp.ones(8)).compile().as_text()
    named = [p for p in phases.hlo_op_paths(text).values()
             if "ens" in phases.path_scopes(p, SCOPES)]
    assert named and any("transpose" in p or "jvp" in p for p in named)


def test_a_traced_window_has_one_objective_span_per_round(tmp_path):
    """A tiny logreg window traced on the CPU: the program's host spans
    land in the profiler's trace on the window's thread, one objective
    read and one dispatch a chunk; the program counts the window's
    device-to-host reads; the chunk's compiled text names its stages. The
    CPU trace has no device plane, so one device op spanning the window
    stands in for the device."""
    import tiny
    cfg, mix = tiny.config("paper-logreg"), tiny.mix()
    chunk = cfg["spec"]["engine"]["chunk"]
    ev = phases.window_events(cfg, mix, seed=2**31 + 21, seconds=0.05,
                              trace_dir=tmp_path)
    rounds = ev["rounds"]
    assert rounds % chunk == 0 and rounds >= chunk
    assert not list(tmp_path.rglob("*.xplane.pb"))
    # a chunk's candidate pass, broadcast stream and batched objective
    # read, the summary's accuracy read
    assert ev["host_syncs"] == 3 * rounds // chunk + 1
    words = {w for p in ev["hlo_paths"].values()
             for w in phases.path_scopes(p, ("ens", "client_grad",
                                             "client_prox", "dp_noise"))}
    assert words == {"ens", "client_grad", "client_prox", "dp_noise"}
    (w0, dw), = [(s, d) for _, n, s, d in ev["host"]
                 if n == tracing.WINDOW_SPAN]
    starts = [s for _, n, s, _ in ev["host"]
              if n == "repro.engine.dispatch"]
    device = [(DEV, "XLA Modules", f"{ev['module']}({i})", s + 1, 1)
              for i, s in enumerate(starts)]
    device.append((DEV, "XLA Ops", "%fusion.1 = f32[] fusion()", w0,
                   dw // 2))
    r = phases.reduce_phases(ev["host"], device)
    spans = r["spans"]
    assert spans["repro.run.objective"][0] == rounds // chunk
    assert spans["repro.engine.dispatch"][0] == rounds // chunk
    assert spans["repro.engine.bookkeeping"][0] == rounds // chunk
    assert spans["repro.engine.candidates"][0] >= rounds // chunk
    assert spans["repro.engine.*"][1] > 0
    assert {k for k, _ in r["idle_phases"]} <= {*spans, tracing.HOST_PYTHON}
    leads = phases.dispatch_leads(ev["host"], device, ev["module"])
    assert leads == [pytest.approx(1e-6)] * (rounds // chunk)
