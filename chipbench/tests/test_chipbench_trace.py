"""The trace reduction on a small synthetic trace."""
from __future__ import annotations

import pathlib
import sys

import pytest

sys.path.insert(0, str(pathlib.Path(__file__).resolve().parents[1]))

import metric_lib  # noqa: E402
import tracing  # noqa: E402

DEV = "/device:TPU:0"
MS = 1_000_000  # ns


def trace():
    """Window 0..100 ms. Device: a while op 10-40 ms holding two ops,
    a third op 35-50 ms (overlapping), one op 90-95 ms, one op before the
    window. The host line of the Python thread (named after the interpreter,
    and holding the window span): a dispatch span 45-60 ms, a wait span
    60-90 ms with a transfer inside it 70-80 ms; another thread's span
    covers the whole window and is not read."""
    host = [("python3", tracing.WINDOW_SPAN, 0, 100 * MS),
            ("python3", "PjitFunction(chunk)", 45 * MS, 15 * MS),
            ("python3", "np.asarray(jax.Array)", 60 * MS, 30 * MS),
            ("python3", "DevicePut", 70 * MS, 10 * MS),
            ("main/1", "CommonPjRtLoadedExecutable::Execute", 0, 100 * MS)]
    device = [
        (DEV, "XLA Modules", "jit_chunk(1)", 10 * MS, 40 * MS),
        (DEV, "XLA Modules", "jit_lambda(2)", 90 * MS, 5 * MS),
        (DEV, "XLA Modules", "jit_early(3)", -20 * MS, 5 * MS),
        (DEV, "XLA Ops", "%while.1 = (f32[4]) while(...)", 10 * MS, 30 * MS),
        (DEV, "XLA Ops", "%sort.2 = f32[9,576] sort(...)", 12 * MS, 10 * MS),
        (DEV, "XLA Ops", "%fusion.3 = f32[4] fusion(...)", 25 * MS, 5 * MS),
        (DEV, "XLA Ops", "%fusion.4 = f32[4] fusion(...)", 35 * MS, 15 * MS),
        (DEV, "XLA Ops", "%fusion.5 = f32[] fusion(...)", 90 * MS, 5 * MS),
        (DEV, "XLA Ops", "%early = f32[] fusion(...)", -20 * MS, 5 * MS),
        ("/device:CUSTOM:Megascale Trace", "XLA Ops", "x", 0, 100 * MS),
    ]
    return host, device


def test_busy_union_idle_and_launches():
    r = tracing.reduce_events(*trace())
    # busy: [10, 50) and [90, 95) -> 45 ms of a 100 ms window
    assert r["window_s"] == pytest.approx(0.100)
    assert r["busy_s"] == pytest.approx(0.045)
    assert r["chips"] == 1
    assert r["launches"] == 2          # the module before the window is out
    assert metric_lib.idle_pct({"trace": r}) == pytest.approx(55.0)


def test_top_ops_leave_out_containers():
    r = tracing.reduce_events(*trace())
    names = [n for n, _ in r["device_ops"]]
    assert names[0].startswith("%fusion.4")
    assert not any(n.startswith("%while") for n in names)
    assert dict(r["device_ops"])["%sort.2 = f32[9,576] sort(...)"] == \
        pytest.approx(0.010)


def test_gaps_are_charged_to_the_innermost_host_span():
    r = tracing.reduce_events(*trace())
    idle = dict(r["idle_gaps"])
    # gaps: [0,10) no python span; [50,90) middle 70 ms -> DevicePut;
    # [95,100) no span
    assert idle[tracing.HOST_PYTHON] == pytest.approx(0.015)
    assert idle["DevicePut"] == pytest.approx(0.040)
    assert "CommonPjRtLoadedExecutable::Execute" not in idle


def test_nothing_to_read_gives_none():
    host, device = trace()
    assert tracing.reduce_events(host[1:], device) is None
    assert tracing.reduce_events(host, []) is None
    ctx = {"trace": None, "rounds": 10, "window_s": 1.0,
           "flops_per_round": 1.0, "peak": {"bf16_flops": 1.0}, "chips": 1}
    assert metric_lib.idle_pct(ctx) is None
    assert metric_lib.mfu(ctx) is None
    assert metric_lib.launches_per_round(ctx) is None


def test_per_round_readers():
    r = tracing.reduce_events(*trace())
    ctx = {"trace": r, "rounds": 4, "window_s": 0.1,
           "flops_per_round": 197e9, "peak": {"bf16_flops": 197e12},
           "chips": 1}
    assert metric_lib.launches_per_round(ctx) == pytest.approx(0.5)
    # 40 rounds/s x 197 GFLOP over 197 TFLOP/s -> 4 %
    assert metric_lib.mfu(ctx) == pytest.approx(4.0)


def test_union_of_unsorted_nested_intervals():
    busy, gaps = tracing.busy_and_gaps([5, 0, 2, 20], [6, 4, 3, 30], 0, 25)
    assert busy == 4 + 1 + 5
    assert gaps == [(4, 5), (6, 20)]


def collective_trace():
    """``trace()`` on two chips. Chip 0 adds an all-to-all 12-20 ms
    overlapping an all-gather start/done pair 18-24 ms, and an all-reduce
    40-45 ms inside its fusion.4; chip 1 runs the same compute one ms
    later with a collective-permute 60-70 ms, an op of its own, and one
    collective before the window."""
    host, device = trace()
    dev1 = "/device:TPU:1"
    device = device + [
        (dev1,) + ev[1:3] + (ev[3] + MS, ev[4]) for ev in device
        if ev[0] == DEV]
    device += [
        (DEV, "XLA Ops", "%all-to-all.1 = f32[33,4] all-to-all(...)",
         12 * MS, 8 * MS),
        (DEV, "XLA Ops", "%all-gather-start.2 = f32[4] all-gather-start()",
         18 * MS, 3 * MS),
        (DEV, "XLA Ops", "%all-gather-done.2 = f32[4] all-gather-done()",
         21 * MS, 3 * MS),
        (DEV, "XLA Ops", "all-reduce.3", 40 * MS, 5 * MS),
        (dev1, "XLA Ops", "%collective-permute-start.4 = f32[4] x()",
         60 * MS, 10 * MS),
        (dev1, "XLA Ops", "%reduce-scatter.5 = f32[1] x()", -10 * MS,
         5 * MS),
    ]
    return host, device


def test_collectives_are_a_union_per_chip_inside_busy():
    r = tracing.reduce_events(*collective_trace())
    assert r["chips"] == 2
    # chip 0: [12, 24) and [40, 45) -> 17 ms; chip 1: [60, 70) -> 10 ms;
    # the one before the window is out
    assert r["collective_s"] == pytest.approx((0.017 + 0.010) / 2)
    # busy: chip 0 [10, 50) + [90, 95); chip 1 [11, 51) + [60, 70) +
    # [91, 96): overlapping ops are counted once
    assert r["busy_s"] == pytest.approx((0.045 + 0.055) / 2)
    assert r["collective_s"] <= r["busy_s"]
    pct = metric_lib.collective_pct({"trace": r})
    assert pct == pytest.approx(100 * 0.027 / 0.100)
    assert 0 < pct <= 100


def test_no_collective_reads_none_and_moves_no_other_key():
    r = tracing.reduce_events(*trace())
    assert r["collective_s"] == 0
    assert metric_lib.collective_pct({"trace": r}) is None
    assert metric_lib.collective_pct({"trace": None}) is None
    # every other key as the reduction read it before collectives were
    assert {k: v for k, v in r.items() if k != "collective_s"} == {
        "window_s": pytest.approx(0.100), "busy_s": pytest.approx(0.045),
        "launches": 2, "chips": 1,
        "device_ops": [["%fusion.4 = f32[4] fusion(...)",
                        pytest.approx(0.015)],
                       ["%sort.2 = f32[9,576] sort(...)",
                        pytest.approx(0.010)],
                       ["%fusion.3 = f32[4] fusion(...)",
                        pytest.approx(0.005)],
                       ["%fusion.5 = f32[] fusion(...)",
                        pytest.approx(0.005)]],
        "idle_gaps": [["DevicePut", pytest.approx(0.040)],
                      [tracing.HOST_PYTHON, pytest.approx(0.015)]]}
    assert tracing.is_collective("%all-to-all.7 = f32[2] all-to-all()")
    assert not tracing.is_collective("%fusion.1 = f32[] all-reduce()")
