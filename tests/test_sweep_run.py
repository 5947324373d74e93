"""Multi-cell sweep driver (repro.launch.sweep_run) + benchmark runner.

Pins the driver's contract:

  * [sweep] FILES -- load_sweep expands the cross-product in grid order
    (last axis fastest, seeds innermost) and rejects malformed tables.
  * RESUMABILITY -- every cell writes an atomic result file; a run killed
    after N of M cells re-executes exactly M-N on rerun, and the merged
    artifact is byte-identical to an uninterrupted run's.
  * DETERMINISM -- the merged artifact is byte-identical between
    --jobs 1 and --jobs 4 (the wall-clock telemetry fields are stripped
    at merge; everything else is a pure function of the spec).
  * FAILURE IS LOUD -- a failing cell fails the invocation (no merge,
    nonzero exit), and a rerun re-executes only the failed cells.

Plus the benchmark-runner satellites: benchmarks/run.py exits nonzero
when any module fails (while still running the others), and
tools/append_bench_trajectory.py replaces re-run labels in place and
warns when a replacement row loses fields.
"""
from __future__ import annotations

import importlib.util
import json
import pathlib
import shutil

import pytest

from repro.launch import sweep_run
from repro.spec import (
    AlgorithmSpec,
    EngineSpec,
    ExperimentSpec,
    FleetSpec,
    PolicySpec,
    SpecError,
    TaskSpec,
    load_sweep,
    sweep,
)
from repro.spec.sweep import parse_sweep_table

ROOT = pathlib.Path(__file__).resolve().parent.parent
TRACE_CSV = ROOT / "tests" / "fixtures" / "device_trace.csv"

BASE = ExperimentSpec(
    name="t", seed=0,
    task=TaskSpec(kind="logreg", d=600, n=14, m=4),
    algorithm=AlgorithmSpec(name="fedepm", rho=0.5, k0=2),
    engine=EngineSpec(name="eager", rounds=2))


def _grid():
    return sweep(BASE, {"algorithm.name": ["fedepm", "sfedavg"]},
                 seeds=[0, 1])


SWEEP_TOML = """\
name = "t"
seed = 0

[task]
kind = "logreg"
d = 600
n = 14
m = 4

[algorithm]
name = "fedepm"
rho = 0.5
k0 = 2

[engine]
name = "eager"
rounds = 2

[sweep]
"algorithm.name" = ["fedepm", "sfedavg"]
seeds = [0, 1]
"""


# ---------------------------------------------------------------------------
# [sweep] table loading
# ---------------------------------------------------------------------------

def test_load_sweep_expands_in_grid_order(tmp_path):
    f = tmp_path / "grid.toml"
    f.write_text(SWEEP_TOML)
    base, cells = load_sweep(f)
    assert base.name == "t" and len(cells) == 4
    # axis outermost, seeds innermost; every cell validated + self-named
    assert [c.name for c in cells] == [
        "t/algorithm.name=fedepm/s0", "t/algorithm.name=fedepm/s1",
        "t/algorithm.name=sfedavg/s0", "t/algorithm.name=sfedavg/s1"]
    assert [(c.algorithm.name, c.seed) for c in cells] == [
        ("fedepm", 0), ("fedepm", 1), ("sfedavg", 0), ("sfedavg", 1)]
    # a plain single-cell file is a 1-cell grid
    f2 = tmp_path / "single.toml"
    f2.write_text(SWEEP_TOML.split("[sweep]")[0])
    base2, cells2 = load_sweep(f2)
    assert len(cells2) == 1 and cells2[0] == base2.validate()


def test_load_sweep_rejects_malformed_tables(tmp_path):
    head = SWEEP_TOML.split("[sweep]")[0]
    for table, match in [
            ('[sweep]\n"algorithm.name" = "fedepm"\n', "list"),
            ('[sweep]\n"algorithm.name" = []\n', "empty"),
            ("[sweep]\nseeds = [0, true]\n", "ints"),
            ("[sweep]\n", "no axes"),
            ('[sweep]\n"algorithm.nope" = [1]\n', "unknown"),
    ]:
        f = tmp_path / "bad.toml"
        f.write_text(head + table)
        with pytest.raises(SpecError, match=match):
            load_sweep(f)
    # axis order = table key order; seeds never an axis
    axes, seeds = parse_sweep_table(
        {"policy.deadline": [0.1], "seeds": [0, 1], "algorithm.k0": [2]})
    assert list(axes) == ["policy.deadline", "algorithm.k0"]
    assert seeds == [0, 1]


# ---------------------------------------------------------------------------
# driver: end-to-end, resume, determinism
# ---------------------------------------------------------------------------

def _merged_bytes(out_dir, cells, records):
    path = pathlib.Path(out_dir) / "merged.json"
    sweep_run.write_merged(path, cells, records, meta={"name": "t"})
    return path.read_bytes()


def test_execute_cells_end_to_end(tmp_path):
    cells = _grid()
    res = sweep_run.execute_cells(cells, out_dir=tmp_path)
    assert res.ok and sorted(res.executed) == sorted(c.name for c in cells)
    assert list(res.records) == [c.name for c in cells]  # grid order
    rec = res.records[cells[0].name]
    assert rec["status"] == "ok" and rec["wall_s"] > 0
    # the default runner attaches run telemetry; per-cell files keep the
    # wall-clock fields, the merged artifact strips them
    assert "wall_s" in rec["summary"]["telemetry"]
    merged = json.loads(_merged_bytes(tmp_path, cells, res.records))
    assert merged["kind"] == "sweep" and merged["n_cells"] == 4
    cell0 = merged["cells"][cells[0].name]
    assert "telemetry" in cell0
    assert "wall_s" not in cell0["telemetry"]
    assert "rounds_per_sec_wall" not in cell0["telemetry"]
    assert cell0["f_final"] == rec["summary"]["f_final"]
    # a second invocation skips every cell (fingerprint match)...
    res2 = sweep_run.execute_cells(cells, out_dir=tmp_path)
    assert res2.ok and not res2.executed and len(res2.skipped) == 4
    # ...but a changed ctx invalidates the fingerprint
    res3 = sweep_run.execute_cells(cells, out_dir=tmp_path,
                                   ctx={"telemetry": False})
    assert res3.ok and len(res3.executed) == 4
    with pytest.raises(ValueError, match="duplicate"):
        sweep_run.execute_cells([cells[0], cells[0]], out_dir=tmp_path)
    with pytest.raises(ValueError, match="unknown cell"):
        sweep_run.execute_cells(cells, out_dir=tmp_path,
                                cell_ctx={"nope": {}})


def test_kill_resume_and_jobs_give_identical_merged(tmp_path, monkeypatch):
    # worker processes are allowed only with the CPU pinned
    monkeypatch.setenv("JAX_PLATFORMS", "cpu")
    cells = _grid()
    # reference: uninterrupted --jobs 1 run
    a = tmp_path / "a"
    res_a = sweep_run.execute_cells(cells, out_dir=a)
    bytes_a = _merged_bytes(a, cells, res_a.records)

    # killed after 2 of 4 cells (max_cells = the deterministic kill)
    b = tmp_path / "b"
    part = sweep_run.execute_cells(cells, out_dir=b, max_cells=2)
    assert not part.ok and len(part.executed) == 2
    assert part.pending == [c.name for c in cells[2:]]
    with pytest.raises(ValueError, match="no ok result"):
        sweep_run.write_merged(b / "merged.json", cells, part.records,
                               meta={})
    # the rerun executes EXACTLY the 4-2 missing cells
    rest = sweep_run.execute_cells(cells, out_dir=b)
    assert rest.ok and len(rest.skipped) == 2
    assert rest.executed == [c.name for c in cells[2:]]
    assert _merged_bytes(b, cells, rest.records) == bytes_a

    # same grid across 4 worker processes: byte-identical artifact
    c = tmp_path / "c"
    res_c = sweep_run.execute_cells(cells, out_dir=c, jobs=4)
    assert res_c.ok
    assert _merged_bytes(c, cells, res_c.records) == bytes_a


def test_failed_cell_is_loud_and_rerun_reexecutes_only_it(tmp_path):
    # a cell that validates but cannot build: trace fleet whose file
    # appears only later (exactly the transient-failure resume story)
    trace = tmp_path / "trace.csv"
    bad = BASE.replace(**{"name": "t/bad"}).replace(
        fleet=FleetSpec(kind="trace", trace_file=str(trace))).validate()
    cells = [*sweep(BASE, {"algorithm.name": ["fedepm", "sfedavg"]}), bad]
    out = tmp_path / "sweep"
    res = sweep_run.execute_cells(cells, out_dir=out)
    assert not res.ok and res.failed == ["t/bad"]
    rec = res.records["t/bad"]
    assert rec["status"] == "failed" and "traceback" in rec
    with pytest.raises(ValueError, match="no ok result"):
        sweep_run.write_merged(out / "merged.json", cells, res.records,
                               meta={})
    # rerun: the ok cells are skipped, the failed one re-executes -- and
    # succeeds now that the fixture exists
    shutil.copy(TRACE_CSV, trace)
    res2 = sweep_run.execute_cells(cells, out_dir=out)
    assert res2.ok and res2.executed == ["t/bad"]
    assert len(res2.skipped) == 2


def test_cli_exit_codes_and_resume(tmp_path):
    f = tmp_path / "grid.toml"
    f.write_text(SWEEP_TOML)
    out = tmp_path / "out"
    argv = ["--spec", str(f), "--out-dir", str(out), "--quiet"]
    assert sweep_run.main([*argv, "--max-cells", "1"]) \
        == sweep_run.EXIT_PENDING
    assert not (out / "merged.json").exists()
    assert sweep_run.main(argv) == sweep_run.EXIT_OK
    merged = json.loads((out / "merged.json").read_text())
    assert merged["n_cells"] == 4 and merged["name"] == "t"
    assert merged["axes"] == {"algorithm.name": ["fedepm", "sfedavg"]}
    assert merged["seeds"] == [0, 1]
    # idempotent: a third run skips everything, same artifact bytes
    before = (out / "merged.json").read_bytes()
    assert sweep_run.main(argv) == sweep_run.EXIT_OK
    assert (out / "merged.json").read_bytes() == before


@pytest.mark.parametrize("platforms", [None, "tpu", "cpu,tpu"])
def test_parallel_jobs_refused_off_cpu(tmp_path, monkeypatch, platforms):
    """An accelerator serves one process: jobs > 1 needs JAX_PLATFORMS=cpu,
    and the refusal comes before any cell runs."""
    if platforms is None:
        monkeypatch.delenv("JAX_PLATFORMS", raising=False)
    else:
        monkeypatch.setenv("JAX_PLATFORMS", platforms)
    with pytest.raises(ValueError, match="JAX_PLATFORMS=cpu"):
        sweep_run.execute_cells(_grid(), out_dir=tmp_path, jobs=2)
    assert not (tmp_path / "cells").exists()


def test_cell_filename_is_safe_and_collision_free():
    a = sweep_run.cell_filename("fig7/fedepm/async/codec-ef")
    assert "/" not in a and a.endswith(".json")
    # names differing only past the truncation point stay distinct
    long_a = sweep_run.cell_filename("x" * 100 + "a")
    long_b = sweep_run.cell_filename("x" * 100 + "b")
    assert long_a != long_b


# ---------------------------------------------------------------------------
# benchmarks/run.py: failures must fail the invocation
# ---------------------------------------------------------------------------

def test_benchmark_runner_exits_nonzero_but_isolates(monkeypatch, capsys):
    from benchmarks import ens_kernel, fig2_accuracy
    from benchmarks import run as bench_run

    def boom(**kw):
        raise RuntimeError("synthetic benchmark failure")

    monkeypatch.setattr(fig2_accuracy, "run", boom)
    monkeypatch.setattr(ens_kernel, "run",
                        lambda **kw: [("ens/stub", 1.0, "ok")])
    rc = bench_run.main(["--quick", "--only", "fig2,ens"])
    out = capsys.readouterr()
    # the failed module is an ERROR row, the later module still ran --
    # and the invocation as a whole reports failure
    assert "fig2/ERROR,0,RuntimeError:synthetic benchmark failure" in out.out
    assert "ens/stub,1.0,ok" in out.out
    assert "fig2" in out.err and rc == 1

    monkeypatch.setattr(fig2_accuracy, "run",
                        lambda **kw: [("fig2/stub", 2.0, "ok")])
    assert bench_run.main(["--quick", "--only", "fig2,ens"]) == 0


def test_benchmark_runner_forwards_jobs_uniformly(monkeypatch, capsys):
    """--jobs reaches EVERY spec-grid module (fig6/fig7/fig8/fig9/
    engine) -- the sweep-driver parallelism knob is uniform, not
    per-module."""
    from benchmarks import (bench_engine, fig6_stragglers, fig7_async,
                            fig8_faults, fig9_privacy)
    from benchmarks import run as bench_run

    seen = {}

    def record(name):
        def fake_run(**kw):
            seen[name] = kw
            return [(f"{name}/stub", 1.0, "ok")]
        return fake_run

    monkeypatch.setattr(fig6_stragglers, "run", record("fig6"))
    monkeypatch.setattr(fig7_async, "run", record("fig7"))
    monkeypatch.setattr(fig8_faults, "run", record("fig8"))
    monkeypatch.setattr(fig9_privacy, "run", record("fig9"))
    monkeypatch.setattr(bench_engine, "run", record("engine"))
    rc = bench_run.main(["--quick", "--jobs", "3",
                         "--only", "fig6,fig7,fig8,fig9,engine"])
    out = capsys.readouterr().out
    assert rc == 0
    assert set(seen) == {"fig6", "fig7", "fig8", "fig9", "engine"}
    for name, kw in seen.items():
        assert kw.get("jobs") == 3, f"{name} did not receive --jobs"
        assert f"{name}/stub,1.0,ok" in out


# ---------------------------------------------------------------------------
# tools/append_bench_trajectory.py: in-place replace + field-loss warning
# ---------------------------------------------------------------------------

def _load_trajectory_tool():
    tool = ROOT / "tools" / "append_bench_trajectory.py"
    spec = importlib.util.spec_from_file_location("append_traj_tool", tool)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def _engine_summary(rps=100.0, with_async=True):
    def eng(r):
        return {"rounds_per_sec": r, "wall_to_target_s": 0.5,
                "rounds_to_target": 10, "host_syncs": 20,
                "host_syncs_per_round": 2.0}
    s = {"config": {"backend": "cpu", "d": 2000, "m": 16, "rounds": 120},
         "engines": {"eager": eng(rps), "scan": eng(rps * 4)},
         "speedup_rounds_per_sec": 4.0, "speedup_wall_to_target": 2.0,
         "target_objective": 0.5}
    if with_async:
        s["async"] = {"config": {"buffer_size": 4, "max_concurrency": 6},
                      "engines": {"eager": {"rounds_per_sec": rps / 2,
                                            "host_syncs": 5,
                                            "host_syncs_per_round": 0.5},
                                  "scan": {"rounds_per_sec": rps,
                                           "host_syncs": 1,
                                           "host_syncs_per_round": 0.1}},
                      "speedup_rounds_per_sec": 2.0}
    return s


def test_trajectory_append_replaces_in_place(tmp_path, capsys):
    tool = _load_trajectory_tool()
    ej = tmp_path / "BENCH_engine.json"
    out = tmp_path / "BENCH_trajectory.json"

    ej.write_text(json.dumps(_engine_summary(rps=100.0)))
    tool.append(ej, out, "pr1")
    ej.write_text(json.dumps(_engine_summary(rps=200.0)))
    tool.append(ej, out, "pr2")
    doc = json.loads(out.read_text())
    assert [r["label"] for r in doc["rows"]] == ["pr1", "pr2"]

    # re-running pr1 replaces ITS row, in place: order is stable and the
    # numbers change
    ej.write_text(json.dumps(_engine_summary(rps=300.0)))
    tool.append(ej, out, "pr1")
    doc = json.loads(out.read_text())
    assert [r["label"] for r in doc["rows"]] == ["pr1", "pr2"]
    assert doc["rows"][0]["eager_rounds_per_sec"] == 300.0
    assert "async_eager_rounds_per_sec" in doc["rows"][0]
    assert capsys.readouterr().err == ""

    # a replacement that LOST the async block warns on stderr
    ej.write_text(json.dumps(_engine_summary(rps=300.0, with_async=False)))
    tool.append(ej, out, "pr1")
    err = capsys.readouterr().err
    assert "warning" in err and "async_eager_rounds_per_sec" in err
    doc = json.loads(out.read_text())
    assert [r["label"] for r in doc["rows"]] == ["pr1", "pr2"]
    assert "async_eager_rounds_per_sec" not in doc["rows"][0]


def _fig9_rows(*, fedepm_snr="True", mask=True):
    rows = [
        {"name": "fig9/fedepm/snr_increases_with_eps", "value": 0.0,
         "derived": fedepm_snr},
        {"name": "fig9/sfedavg/snr_increases_with_eps", "value": 0.0,
         "derived": "True"},
        {"name": "fig9/fedepm/cr_stable_in_eps", "value": 0.0,
         "derived": "True"},
        {"name": "fig9/sfedavg/cr_stable_in_eps", "value": 0.0,
         "derived": "True"},
        {"name": "fig9/fedepm_smallest_SNR", "value": 0.0,
         "derived": "True"},
    ]
    if mask:
        rows.append({"name": "fig9/fedepm/secure_agg/mask_overhead",
                     "value": 7680.0, "derived": "mask_attempts=240"})
    return rows


def test_trajectory_fig9_merge(tmp_path):
    tool = _load_trajectory_tool()
    ej = tmp_path / "BENCH_engine.json"
    f9 = tmp_path / "fig9_privacy.json"
    out = tmp_path / "BENCH_trajectory.json"
    ej.write_text(json.dumps(_engine_summary()))

    f9.write_text(json.dumps(_fig9_rows()))
    tool.append(ej, out, "pr1", fig9_json=f9)
    row = json.loads(out.read_text())["rows"][0]
    assert row["fig9_snr_increases_with_eps"] is True
    assert row["fig9_cr_stable_in_eps"] is True
    assert row["fig9_fedepm_smallest_snr"] is True
    assert row["fig9_secure_agg_mask_bytes"] == 7680.0

    # per-algorithm claim verdicts are ANDed: one failing algorithm
    # flips the trajectory field (derived is a stringified bool)
    f9.write_text(json.dumps(_fig9_rows(fedepm_snr="False")))
    tool.append(ej, out, "pr1", fig9_json=f9)
    row = json.loads(out.read_text())["rows"][0]
    assert row["fig9_snr_increases_with_eps"] is False

    # a missing claim row is a loud error, not a silently absent field
    f9.write_text(json.dumps(
        [r for r in _fig9_rows() if "smallest" not in r["name"]]))
    with pytest.raises(SystemExit, match="fedepm_smallest_SNR"):
        tool.append(ej, out, "pr1", fig9_json=f9)

    # without --fig9-json the row simply lacks the fields (old history
    # rows stay valid)
    tool.append(ej, out, "pr2")
    row = json.loads(out.read_text())["rows"][1]
    assert not any(k.startswith("fig9_") for k in row)
