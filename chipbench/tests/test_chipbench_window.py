"""The set-up and window loop, called as functions at a tiny CPU size."""
from __future__ import annotations

import pathlib
import sys
import time

import pytest

sys.path.insert(0, str(pathlib.Path(__file__).resolve().parent))

import tiny  # noqa: E402

import harness  # noqa: E402  (put on the path by tiny)


@pytest.mark.parametrize("name", ["paper-logreg", "smollm-135m"])
def test_one_run_call_with_a_whole_number_of_chunks(name, monkeypatch):
    from repro.spec.build import RunHandle
    calls = []
    real = RunHandle.run

    def counted(self, report=None):
        calls.append(self.spec.engine.rounds)
        return real(self, report)

    monkeypatch.setattr(RunHandle, "run", counted)
    cfg, mix = tiny.config(name), tiny.mix()
    chunk = cfg["spec"]["engine"]["chunk"]
    run = harness.run_window(cfg, mix, seed=2**31 + 11, seconds=0.5,
                             trace=False, t_start=time.perf_counter())
    # first chunk, timed chunks, then the window: one call
    assert calls[0] == chunk and len(calls) >= 3
    assert all(c == chunk for c in calls[:-1])
    assert calls[-1] == run["rounds"] and run["rounds"] % chunk == 0
    assert run["compiles_in_window"] == 0
    assert run["setup_s"] > 0 and run["window_s"] > 0
    ok, checks = harness.check(cfg, mix, 2**31 + 11, run["prog"])
    assert ok, checks
    assert set(checks) == {"loss_gap", "grad_gap", "change_gap",
                           "sim_mismatch"}
    ctx = harness.metric_context(
        cfg, run, {run["device"]["kind"]: {"bf16_flops": 1.0}})
    e2e = harness.read_metrics(ctx, [
        {"name": "setup_s", "unit": "s"},
        {"name": "rounds_per_s", "unit": "rounds/s"},
        {"name": "tokens_per_s", "unit": "tokens/s"}])
    assert e2e["rounds_per_s"]["value"] == pytest.approx(
        run["rounds"] / run["window_s"])
    assert e2e["setup_s"]["value"] == run["setup_s"]
    # logreg has no tokens: its reader finds nothing and the metric is left out
    assert ("tokens_per_s" in e2e) == (name == "smollm-135m")


def test_same_seed_same_inputs_other_seed_other_inputs():
    cfg = tiny.config("paper-logreg")
    task = harness.config_module(cfg["_file"])
    a, _, _ = task.make_data(cfg, 5)
    b, _, _ = task.make_data(cfg, 5)
    c, _, _ = task.make_data(cfg, 6)
    assert (a["x"] == b["x"]).all() and (a["y"] == b["y"]).all()
    assert a["x"].shape == c["x"].shape and not (a["x"] == c["x"]).all()


def test_window_stops_short_of_the_configurations_horizon():
    cfg, mix = tiny.config("paper-logreg"), tiny.mix()
    cfg["max_rounds"] = 1      # already past it after set-up: one chunk
    run = harness.run_window(cfg, mix, seed=2**31 + 12, seconds=0.5,
                             trace=False, t_start=time.perf_counter())
    assert run["rounds"] == cfg["spec"]["engine"]["chunk"]
