"""Run telemetry (repro.telemetry): the recorder must be invisible to the
trajectory (bit-for-bit on/off across every policy and both engines), the
scan engine must reconstruct the eager event stream exactly, and the sinks
(JSONL, summary block, Perfetto trace) must round-trip/validate. Plus the
ByteLedger snapshot/delta API and the event->metric derivation rules."""
import json

import jax
import jax.numpy as jnp
import numpy as np
import pytest

try:
    import hypothesis
    import hypothesis.strategies as st
except ImportError:                       # optional, like the kernel tests
    hypothesis = None

from repro import spec as xspec
from repro.core import fedepm
from repro.core.tasks import make_logistic_loss
from repro.data import synth
from repro.data.partition import partition_iid
from repro.launch import simulate
from repro.sim import CodecConfig, FedSim, SimConfig, make_profiles, \
    run_rounds
from repro.sim.transport import ByteLedger
from repro.spec.types import SpecError
from repro.telemetry import (
    EVENT_KINDS,
    Event,
    EventRecorder,
    MetricsRegistry,
    NULL_RECORDER,
    read_events_jsonl,
    to_trace,
    validate_trace,
    write_events_jsonl,
)

M = 12
N = 10

POLICIES = [
    ("sync", {}),
    ("deadline", {"deadline": 0.002}),
    ("adaptive", {"deadline_slack": 1.5, "ewma_beta": 0.5}),
    ("overselect", {"overselect_factor": 1.5}),
    ("async", {"buffer_size": 3, "max_concurrency": 4}),
]
CLOCKED = POLICIES[:4]


@pytest.fixture(scope="module")
def task():
    X, y = synth.adult_like(d=800, n=N, seed=0)
    batches = jax.tree_util.tree_map(jnp.asarray,
                                     partition_iid(X, y, m=M, seed=0))
    return batches, make_logistic_loss()


def _build(task, policy, kw, *, codec=None, availability=0.9, eps=0.1,
           seed=9, profile_seed=5, telemetry=None):
    batches, loss = task
    cfg = fedepm.FedEPMConfig.paper_defaults(
        m=M, rho=0.5, k0=2, eps_dp=eps, sensitivity_clip=1.0)
    s0 = fedepm.init_state(jax.random.PRNGKey(0), jnp.zeros(N), cfg)
    sim_cfg = SimConfig(policy=policy, latency="pareto", latency_alpha=1.3,
                        seed=seed, codec=codec, **kw)
    return FedSim(alg="fedepm", cfg=cfg, state=s0, batches=batches,
                  loss_fn=loss,
                  profiles=make_profiles(M, seed=profile_seed,
                                         availability=availability),
                  sim=sim_cfg, telemetry=telemetry)


def _run(sim, rounds, engine):
    if engine == "eager":
        sim.run(rounds)
    else:
        run_rounds(sim, rounds, chunk=2)


# ---------------------------------------------------------------------------
# the overhead contract: recording cannot perturb the trajectory
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("engine", ["eager", "scan"])
@pytest.mark.parametrize("policy,kw", POLICIES, ids=[p for p, _ in POLICIES])
def test_recorder_on_off_bitforbit(task, policy, kw, engine):
    """Telemetry-on state/clock/metrics/ledger == telemetry-off, exactly,
    under every policy and both engines (the recorder reads host values
    only -- no RNG draws, no jit dispatches)."""
    off = _build(task, policy, kw)
    on = _build(task, policy, kw, telemetry=EventRecorder())
    _run(off, 5, engine)
    _run(on, 5, engine)
    for name, a, b in zip(off.state._fields, on.state, off.state):
        assert np.array_equal(np.asarray(a), np.asarray(b)), \
            f"state leaf {name!r} diverged with telemetry on"
    assert on.t == off.t
    assert on.round_idx == off.round_idx
    assert on.metrics == off.metrics
    assert on.ledger.total_up == off.ledger.total_up
    assert on.ledger.total_down == off.ledger.total_down
    assert on.telemetry.events, "enabled recorder captured nothing"
    assert off.telemetry is NULL_RECORDER


@pytest.mark.parametrize("policy,kw", CLOCKED, ids=[p for p, _ in CLOCKED])
def test_eager_scan_event_streams_identical(task, policy, kw):
    """The scan engine's bookkeeping loop reconstructs the eager event
    stream EXACTLY (same kinds, timestamps, clients, attrs), including
    across chunk boundaries."""
    eager = _build(task, policy, kw, telemetry=EventRecorder())
    scan = _build(task, policy, kw, telemetry=EventRecorder())
    eager.run(5)
    run_rounds(scan, 3, chunk=2)
    run_rounds(scan, 2)
    assert scan.telemetry.events == eager.telemetry.events


def test_codec_and_ledger_events(task):
    """A lossy codec run emits codec_encode with the codec's parameters
    and ledger_record events whose running totals match the ledger."""
    codec = CodecConfig(topk_frac=0.5, bits=8)
    sim = _build(task, "sync", {}, codec=codec, eps=0.0,
                 telemetry=EventRecorder())
    sim.run(4)
    encs = [e for e in sim.telemetry.events if e.kind == "codec_encode"]
    assert encs and all(e.attrs["bits"] == 8 and e.attrs["topk_frac"] == 0.5
                        for e in encs)
    recs = [e for e in sim.telemetry.events if e.kind == "ledger_record"]
    assert recs
    assert recs[-1].attrs["total_up"] == sim.ledger.total_up
    assert recs[-1].attrs["total_down"] == sim.ledger.total_down
    # per-round deltas sum to the totals
    assert sum(e.attrs["up"] for e in recs) == pytest.approx(
        sim.ledger.total_up)


# ---------------------------------------------------------------------------
# sinks: JSONL round-trip, Perfetto validation
# ---------------------------------------------------------------------------

def test_jsonl_roundtrip_exact(task, tmp_path):
    """read(write(events)) == events, exactly -- every field of every
    event, including float timestamps and attr payloads."""
    sim = _build(task, "async", {"buffer_size": 3, "max_concurrency": 4},
                 codec=CodecConfig(topk_frac=0.5, bits=8), eps=0.0,
                 telemetry=EventRecorder())
    sim.run(6)
    path = tmp_path / "events.jsonl"
    write_events_jsonl(sim.telemetry.events, path)
    assert read_events_jsonl(path) == sim.telemetry.events


@pytest.mark.parametrize("policy,kw", POLICIES, ids=[p for p, _ in POLICIES])
def test_trace_export_validates(task, policy, kw):
    """Every exported trace event carries the Chrome trace_event required
    keys and the client events land on per-client tracks (pid 2)."""
    sim = _build(task, policy, kw, telemetry=EventRecorder())
    sim.run(5)
    trace = to_trace(sim.telemetry.events, label=policy)
    assert validate_trace(trace) == []
    evs = trace["traceEvents"]
    assert all({"name", "ph", "ts", "pid", "tid"} <= set(e) for e in evs)
    client_tids = {e["tid"] for e in evs
                   if e["pid"] == 2 and e["ph"] != "M"}
    assert len(client_tids) > 1, "expected one track per client"
    names = {e["name"] for e in evs if e["ph"] == "M"}
    assert "process_name" in names and "thread_name" in names


def test_validate_trace_flags_problems():
    assert validate_trace({}) != []
    assert validate_trace({"traceEvents": []}) != []
    bad = {"traceEvents": [{"name": "x", "ph": "i", "ts": 0.0, "pid": 1}]}
    assert any("tid" in p for p in validate_trace(bad))
    neg = {"traceEvents": [{"name": "x", "ph": "X", "ts": 1.0, "pid": 1,
                            "tid": 0, "dur": -5.0}]}
    assert validate_trace(neg) != []


# ---------------------------------------------------------------------------
# per-client timestamp monotonicity
# ---------------------------------------------------------------------------

def _assert_monotone_per_client(events):
    per_client: dict = {}
    for ev in events:
        if ev.client is None:
            continue
        last = per_client.get(ev.client)
        assert last is None or ev.ts >= last, \
            (ev.client, last, ev.ts, ev.kind)
        per_client[ev.client] = ev.ts
    assert per_client, "no client-scoped events recorded"


@pytest.mark.parametrize("policy,kw", POLICIES, ids=[p for p, _ in POLICIES])
def test_timestamps_monotone_per_client(task, policy, kw):
    sim = _build(task, policy, kw, telemetry=EventRecorder())
    sim.run(6)
    _assert_monotone_per_client(sim.telemetry.events)


if hypothesis is not None:
    @hypothesis.settings(deadline=None, max_examples=10)
    @hypothesis.given(seed=st.integers(0, 2**16),
                      profile_seed=st.integers(0, 2**16))
    def test_timestamps_monotone_property(task, seed, profile_seed):
        """Any fleet/arrival randomization keeps each client's event track
        monotone in simulated time (the async event loop's clock and the
        clocked policies' min(arrival, dur) clamp both guarantee it)."""
        sim = _build(task, "async",
                     {"buffer_size": 2, "max_concurrency": 3},
                     seed=seed, profile_seed=profile_seed,
                     telemetry=EventRecorder())
        sim.run(4)
        _assert_monotone_per_client(sim.telemetry.events)


# ---------------------------------------------------------------------------
# ByteLedger snapshot/delta
# ---------------------------------------------------------------------------

def test_ledger_snapshot_delta():
    led = ByteLedger(4)
    s0 = led.snapshot()
    led.record_round(down_mask=np.array([True, True, False, False]),
                     up_mask=np.array([True, False, False, False]),
                     down_bytes=100, up_bytes=40)
    s1 = led.snapshot()
    assert led.delta(s0) == {"up": 40.0, "down": 200.0}
    assert s1.up == led.total_up and s1.down == led.total_down
    led.record_round(down_mask=np.array([False, False, True, True]),
                     up_mask=np.array([False, False, True, True]),
                     down_bytes=100, up_bytes=40.5)  # float path
    assert led.delta(s1) == {"up": 81.0, "down": 200.0}
    assert led.delta(s0)["up"] == pytest.approx(121.0)
    # the O(1) totals agree with the per-client array sums
    assert led.total_up == pytest.approx(float(led.up.sum()))
    assert led.total_down == pytest.approx(float(led.down.sum()))


# ---------------------------------------------------------------------------
# metrics registry: event-stream derivation
# ---------------------------------------------------------------------------

def test_registry_replay_reproduces_summary(task):
    """Metrics are a pure fold over the event stream: replaying a run's
    events through a fresh registry reproduces the summary exactly."""
    sim = _build(task, "async", {"buffer_size": 3, "max_concurrency": 4},
                 telemetry=EventRecorder())
    sim.run(6)
    fresh = MetricsRegistry()
    for ev in sim.telemetry.events:
        fresh.observe(ev)
    assert fresh.summary() == sim.telemetry.registry.summary()


def test_registry_derivation_rules():
    reg = MetricsRegistry()
    reg.observe(Event(0.0, "round_start", 0, None, {"policy": "sync"}))
    reg.observe(Event(0.0, "dispatch", 0, 1, {"arrival_s": 0.5}))
    reg.observe(Event(0.5, "upload_arrival", 0, 1, {}))
    reg.observe(Event(1.0, "merge", 0, 1, {"staleness": 2, "gamma": 0.5}))
    reg.observe(Event(1.0, "ledger_record", 0, None,
                      {"up": 10.0, "down": 20.0}))
    reg.observe(Event(2.0, "abandon", 1, None, {"n_contacted": 0}))
    s = reg.summary()
    assert s["counters"] == {"rounds": 1.0, "dispatches": 1.0,
                             "uploads": 1.0, "merges": 1.0,
                             "abandoned_rounds": 1.0,
                             "bytes_up": 10.0, "bytes_down": 20.0}
    assert s["gauges"]["staleness"] == 2
    assert s["histograms"]["staleness"]["dist"] == {"2": 1}
    assert s["series"]["bytes_up"] == [[1.0, 10.0]]


def test_recorder_rejects_unknown_kind():
    rec = EventRecorder()
    with pytest.raises(ValueError, match="unknown event kind"):
        rec.event("warp_drive", ts=0.0, round_idx=0)
    assert set(EVENT_KINDS) == {
        "round_start", "dispatch", "upload_arrival", "merge", "abandon",
        "codec_encode", "ledger_record",
        "upload_drop", "retry", "duplicate_discard", "quarantine",
        "privacy_charge", "mask_exchange"}


# ---------------------------------------------------------------------------
# spec + RunHandle integration (the acceptance scenario)
# ---------------------------------------------------------------------------

def _async_spec(**tel):
    return xspec.ExperimentSpec(
        name="tel-accept", seed=3,
        task=xspec.TaskSpec(kind="logreg", d=400, n=N, m=M),
        algorithm=xspec.AlgorithmSpec(name="fedepm", rho=0.5, k0=2),
        fleet=xspec.FleetSpec(kind="synthetic", latency="pareto",
                              latency_alpha=1.2),
        policy=xspec.PolicySpec(name="async", buffer_size=3,
                                max_concurrency=4),
        engine=xspec.EngineSpec(name="eager", rounds=6),
        telemetry=xspec.TelemetrySpec(**tel))


def test_runhandle_summary_and_sinks(tmp_path):
    """The fig7-style acceptance run: JSONL + summary series + loadable
    trace, with the objective trajectory bit-for-bit identical to
    telemetry-off and the historical summary schema untouched."""
    ej, tr = tmp_path / "ev.jsonl", tmp_path / "trace.json"
    on = _async_spec(enabled=True, events_jsonl=str(ej),
                     trace_out=str(tr)).validate().build().run()
    off = _async_spec().validate().build().run()
    tel = on.pop("telemetry")
    assert on == off, "telemetry changed the trajectory or summary schema"
    for k in ("bytes_up", "bytes_down", "staleness", "in_flight",
              "stalled", "objective"):
        assert tel["series"].get(k), (k, sorted(tel["series"]))
    assert tel["counters"]["merges"] > 0
    assert tel["wall_s"] > 0 and tel["host_syncs"] > 0
    assert len(read_events_jsonl(ej)) == tel["events"]
    trace = json.loads(tr.read_text())
    assert validate_trace(trace) == []


def test_scan_engine_summary_matches_eager_with_telemetry():
    """engine=scan under telemetry: same f_final as eager, same series."""
    eager = _async_spec(enabled=True).validate()
    scan = eager.replace(**{"engine.name": "scan"}).validate()
    a, b = eager.build().run(), scan.build().run()
    assert a["f_final"] == b["f_final"]
    assert a["telemetry"]["counters"] == b["telemetry"]["counters"]


def test_telemetry_spec_validation():
    with pytest.raises(SpecError, match="enabled"):
        _async_spec(trace_out="x.json").validate()
    with pytest.raises(SpecError, match="enabled"):
        _async_spec(events_jsonl="x.jsonl").validate()
    with pytest.raises(SpecError):
        _async_spec(enabled=True, trace_out="").validate()
    _async_spec(enabled=True).validate()          # sinks are optional
    # dict round-trip keeps the section
    spec = _async_spec(enabled=True, trace_out="t.json")
    again = xspec.ExperimentSpec.from_dict(spec.to_dict())
    assert again.telemetry == spec.telemetry


# ---------------------------------------------------------------------------
# CLI glue
# ---------------------------------------------------------------------------

def test_cli_telemetry_flags(tmp_path):
    """--events-out/--trace-out imply --telemetry; the summary gains the
    telemetry block and stays otherwise identical to a flag-free run."""
    ej = tmp_path / "ev.jsonl"
    tr = tmp_path / "trace.json"
    base = ["--alg", "fedepm", "--aggregation", "async",
            "--buffer-size", "3", "--latency", "pareto",
            "--m", "8", "--d", "500", "--rounds", "4", "--seed", "3",
            "--quiet"]
    on_p, off_p = tmp_path / "on.json", tmp_path / "off.json"
    assert simulate.main(base + ["--json", str(on_p),
                                 "--events-out", str(ej),
                                 "--trace-out", str(tr)]) == 0
    assert simulate.main(base + ["--json", str(off_p)]) == 0
    on = json.loads(on_p.read_text())
    off = json.loads(off_p.read_text())
    tel = on.pop("telemetry")
    assert on == off
    assert tel["events"] == len(read_events_jsonl(ej))
    assert validate_trace(json.loads(tr.read_text())) == []


def test_cli_spec_telemetry_override(tmp_path):
    """--telemetry on top of --spec enables recording for a spec file that
    has no [telemetry] section."""
    import pathlib
    spec_path = str(pathlib.Path(__file__).parent.parent
                    / "examples" / "specs" / "fig7_async.toml")
    out = tmp_path / "s.json"
    rc = simulate.main(["--spec", spec_path,
                        "--rounds", "3", "--telemetry", "--quiet",
                        "--json", str(out)])
    assert rc == 0
    s = json.loads(out.read_text())
    assert s["telemetry"]["counters"]["rounds"] >= 1


@pytest.mark.parametrize("fails", ["start_trace", "stop_trace"])
def test_jax_profile_failure_raises(monkeypatch, tmp_path, fails):
    """A requested trace that cannot start or stop fails the run instead
    of warning; no trace dir means the profiler is never touched."""
    from repro.telemetry import jax_profile

    def boom(*a, **k):
        raise RuntimeError(f"{fails} failed")

    monkeypatch.setattr(jax.profiler, "start_trace", lambda d: None)
    monkeypatch.setattr(jax.profiler, "stop_trace", lambda: None)
    monkeypatch.setattr(jax.profiler, fails, boom)
    with pytest.raises(RuntimeError, match=fails):
        with jax_profile(tmp_path / "trace"):
            pass
    with jax_profile(None):
        pass
