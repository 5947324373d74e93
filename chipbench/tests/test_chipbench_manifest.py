"""BENCHMARK.json against the benchmark's files, the FLOP functions, the
peak table, and ``run.py`` without an accelerator."""
from __future__ import annotations

import json
import os
import pathlib
import re
import subprocess
import sys

import pytest

BENCH = pathlib.Path(__file__).resolve().parents[1]
ROOT = BENCH.parent
sys.path[:0] = [str(BENCH), str(BENCH / "tests"), str(ROOT / "src")]

import harness  # noqa: E402
import run as bench_run  # noqa: E402

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")
MANIFEST = harness.load_json(ROOT / "BENCHMARK.json")
CELLS = [w["name"] for w in MANIFEST["workloads"]]


def test_names_units_and_keys():
    assert set(MANIFEST) == {"command", "paths", "run_seconds", "configs",
                             "workloads", "end_to_end", "per_layer"}
    metrics = MANIFEST["end_to_end"] + MANIFEST["per_layer"]
    names = ([c["name"] for c in MANIFEST["configs"]] + CELLS
             + [m["name"] for m in metrics]
             + [w["traffic"] for w in MANIFEST["workloads"]]
             + [k for c in MANIFEST["configs"] for k in c["reduced"]])
    assert all(NAME.match(n) for n in names), names
    assert all(UNIT.match(m["unit"]) for m in metrics)
    assert len({m["name"] for m in metrics}) == len(metrics)
    assert "setup_s" in {m["name"] for m in MANIFEST["end_to_end"]}
    texts = ([x["why"] for x in MANIFEST["configs"] + MANIFEST["workloads"]]
             + [c["source"] for c in MANIFEST["configs"]]
             + [m["layer"] for m in MANIFEST["per_layer"]]
             + MANIFEST["command"])
    assert all(0 < len(t) <= 200 and "\n" not in t and "\t" not in t
               for t in texts)
    for m in MANIFEST["end_to_end"]:
        assert 0 < m["bound"] <= 0.25 and m["source"] in (
            "host_clock", "device_trace")
    for c in MANIFEST["configs"]:
        assert (ROOT / c["file"]).exists()
        assert (ROOT / c["file"]).with_suffix(".py").exists()


@pytest.mark.parametrize("cell", CELLS)
def test_every_cell_builds_a_spec(cell):
    from repro.spec import ExperimentSpec
    w, entry, cfg, mix = bench_run.cell_files(MANIFEST, cell)
    spec = ExperimentSpec.from_dict(harness.spec_dict(cfg, mix, 2**31 + 7,
                                                     3 * 8))
    spec.validate()
    assert spec.engine.name == "scan"
    assert spec.engine.chunk == cfg["spec"]["engine"]["chunk"]
    # a cell spans one chip or a four-chip host; four only where its
    # configuration's client axis lies over the four, whole clients a chip
    assert w["chips"] in (1, 4)
    assert w["chips"] == harness.mesh_chips(cfg)
    if w["chips"] > 1:
        assert cfg["spec"]["engine"]["mesh"] == w["chips"]
        assert cfg["spec"]["task"]["m"] % w["chips"] == 0
    four = [x for x in MANIFEST["workloads"] if x["chips"] == 4]
    assert len(four) <= max(1, len(CELLS) // 2)
    assert mix["_file"].with_suffix(".py").exists()
    for m in MANIFEST["per_layer"] + MANIFEST["end_to_end"]:
        assert (BENCH / "metrics" / f"{m['name']}.py").exists()


def test_a_cell_whose_chips_are_not_its_mesh_is_refused():
    def with_chips(name, chips):
        cells = [{**w, "chips": chips} if w["name"] == name else w
                 for w in MANIFEST["workloads"]]
        return {**MANIFEST, "workloads": cells}

    for w in MANIFEST["workloads"]:
        other = 1 if w["chips"] == 4 else 4
        with pytest.raises(ValueError, match="chips"):
            bench_run.cell_files(with_chips(w["name"], other), w["name"])


@pytest.mark.parametrize("metric", [m["name"] for m in MANIFEST["per_layer"]])
def test_per_layer_cells_report_what_it_moves(metric):
    m = {x["name"]: x for x in MANIFEST["per_layer"]}[metric]
    e2e = {x["name"]: x for x in MANIFEST["end_to_end"]}
    moved = e2e[m["moves"]]
    for cell in m.get("workloads", CELLS):
        assert cell in moved.get("workloads", CELLS)


def test_flop_counts():
    lm = harness.load_json(BENCH / "configs" / "smollm-135m.json")
    task = harness.config_module(BENCH / "configs" / "smollm-135m.json")
    assert task.n_params(lm) == 134_515_008
    # 6 N plus 12 L d T at T = 2048
    assert task.flops_per_token(lm) == 6 * 134_515_008 + 12 * 30 * 576 * 2048
    assert task.flops_per_token(lm) == 1_231_763_328
    assert task.tokens_per_round(lm) == 4 * 2 * 2048
    assert task.flops_per_round(lm) == 1_231_763_328 * 16384
    m8 = harness.load_json(BENCH / "configs" / "smollm-135m-m8.json")
    task8 = harness.config_module(BENCH / "configs" / "smollm-135m-m8.json")
    assert task8.n_params(m8) == task.n_params(lm)
    assert task8.tokens_per_round(m8) == 8 * 2 * 2048
    assert task8.flops_per_round(m8) == 2 * task.flops_per_round(lm)
    lr = harness.load_json(BENCH / "configs" / "paper-logreg.json")
    task = harness.config_module(BENCH / "configs" / "paper-logreg.json")
    assert task.flops_per_round(lr) == 2_532_432
    assert task.tokens_per_round(lr) is None


def test_lm_params_match_the_reference_init():
    import jax
    cfg = harness.load_json(BENCH / "configs" / "smollm-135m.json")
    task = harness.config_module(BENCH / "configs" / "smollm-135m.json")
    shapes = jax.eval_shape(lambda: task.init(cfg, 0))
    assert sum(int(x.size) for x in jax.tree_util.tree_leaves(shapes)) \
        == task.n_params(cfg)


def test_unknown_device_kind_is_refused():
    peaks = harness.load_json(BENCH / "peaks.json")
    assert peaks["TPU v5 lite"]["bf16_flops"] == 197e12
    with pytest.raises(ValueError, match="no peaks"):
        bench_run.peak_row(peaks, "TPU v99")


def test_without_an_accelerator_it_exits_nonzero_and_prints_no_result():
    env = {**os.environ, "JAX_PLATFORMS": "cpu"}
    p = subprocess.run(
        [sys.executable, str(BENCH / "run.py"), "--workload", CELLS[0],
         "--seed", str(2**31 + 3), "--seconds", "1", "--trace", "0"],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=300)
    assert p.returncode != 0
    assert "accelerator" in p.stderr
    for line in p.stdout.splitlines():
        with pytest.raises(ValueError):
            json.loads(line)


def test_settle_cache_keeps_what_the_first_run_left(tmp_path):
    cache = tmp_path / "jax"
    cache.mkdir()
    (cache / "a-cache").write_bytes(b"a")
    bench_run.settle_cache(cache, "x")         # x's first run lists its files
    (cache / "b-cache").write_bytes(b"b")      # a later run's seed's program
    (cache / "a-cache").write_bytes(b"a2")     # a listed file stays
    bench_run.settle_cache(cache, "x")
    assert sorted(p.name for p in cache.iterdir()) == ["a-cache"]
    (cache / "c-cache").write_bytes(b"c")      # another cell's first run
    bench_run.settle_cache(cache, "y")
    (cache / "d-cache").write_bytes(b"d")
    bench_run.settle_cache(cache, "x")         # keeps what either cell listed
    assert sorted(p.name for p in cache.iterdir()) == ["a-cache", "c-cache"]
    bench_run.settle_cache(tmp_path / "absent", "x")  # no cache yet: nothing
