"""Spec -> runnable experiment: the one builder behind every entry point.

``build(spec)`` materializes an :class:`~repro.spec.types.ExperimentSpec`
into a :class:`RunHandle`: the task data, the algorithm config/state, the
device fleet, and a configured :class:`repro.sim.FedSim` -- the same
construction the simulate CLI's historical ``build_sim`` performed from
argparse flags, executed through the registries so registered extensions
build through the same path as the built-ins. Trajectories are bit-for-bit
identical to the legacy flag path (tests/test_spec.py pins this against
the golden NPZ).

Task data is memoized per resolved :class:`TaskSpec` (bounded FIFO): two
cells of a sweep over the same task share ONE device copy of the batches,
which also keeps ``id(batches)`` stable so the jit caches in
``repro.sim.server``/``repro.sim.engine`` hit across ``build()`` calls --
a grid of sims compiles each program once, not once per cell.

``RunHandle.run`` owns the execution loop both CLIs and the benchmarks
reuse: the eager per-round path and the fused scan-chunk path (identical
trajectories, docs/perf.md), per-round objective tracking where the
broadcast point is a flat vector (the logreg task; a scan chunk's values
come back in one batched read; LM pytrees are evaluated at chunk
boundaries instead), and the paper's termination rule under
``engine.terminate``.
"""
from __future__ import annotations

import contextlib
import dataclasses
import time
from typing import Callable

import jax
import jax.numpy as jnp
import numpy as np
from jax.profiler import TraceAnnotation

from repro.core import fedepm
from repro.sim import FedSim, SimConfig, run_rounds
from repro.sim.server import fifo_cache_get
from repro.spec import registry
from repro.spec.types import ExperimentSpec

# task-data memo: resolved TaskSpec -> TaskData. Bounded: each entry pins
# a full dataset on device (the same reason the sim's jit caches are
# bounded), so a long sweep over many tasks cannot leak one per cell.
_TASK_CACHE: dict = {}
# jitted objective/grad-norm programs keyed by (loss_fn, batches identity);
# stable across RunHandles because _TASK_CACHE keeps both alive
_OBJ_CACHE: dict = {}


def task_data(spec: ExperimentSpec) -> registry.TaskData:
    """Materialize (memoized) the spec's task."""
    task = spec.task
    resolved = dataclasses.replace(
        task, seed=task.seed if task.seed is not None else spec.seed)
    entry = registry.TASKS[resolved.kind]
    return fifo_cache_get(_TASK_CACHE, resolved,
                          lambda: entry.build(resolved, resolved.seed),
                          cap=8)


# SimConfig's own dataclass defaults are the single source for unset
# policy knobs (deadline=inf, overselect_factor, buffer_size, ...): an
# all-None spec is exactly the historical CLI behaviour, and a default
# changed in sim/server.py propagates here without a second edit
SIM_KNOB_DEFAULTS: dict = {
    f.name: f.default for f in dataclasses.fields(SimConfig)}


def _sim_config(spec: ExperimentSpec) -> SimConfig:
    """PolicySpec/FleetSpec/CodecSpec -> SimConfig, filling SimConfig's
    own default for every unset policy knob."""
    pol, fleet = spec.policy, spec.fleet
    codec = registry.CODECS[spec.codec.name].build(spec.codec)

    def default(knob):
        v = getattr(pol, knob)
        return SIM_KNOB_DEFAULTS[knob] if v is None else v

    return SimConfig(
        policy=pol.name,
        deadline=default("deadline"),
        overselect_factor=default("overselect_factor"),
        latency=fleet.latency, latency_sigma=fleet.latency_sigma,
        latency_alpha=fleet.latency_alpha, seed=spec.seed, codec=codec,
        buffer_size=default("buffer_size"),
        staleness_exp=default("staleness_exp"),
        max_concurrency=default("max_concurrency"),
        deadline_slack=default("deadline_slack"),
        ewma_beta=default("ewma_beta"),
        faults=_fault_config(spec),
        privacy=_privacy_config(spec))


def _fault_config(spec: ExperimentSpec):
    """[faults] -> FaultConfig, or None when every fault rate is zero (the
    zero-rate spec builds the exact pre-fault sim, golden-pinned)."""
    fl = spec.faults
    if not (fl.drop_rate > 0 or fl.transient_rate > 0
            or fl.corrupt_rate > 0 or fl.duplicate_rate > 0):
        return None
    from repro.sim.faults import FaultConfig
    # dedicated stream, decorrelated from the arrival RNG by default so
    # fault decisions never perturb (or depend on) the latency draws
    seed = fl.seed if fl.seed is not None else spec.seed ^ 0xFA17
    return FaultConfig(
        drop_rate=fl.drop_rate, transient_rate=fl.transient_rate,
        corrupt_rate=fl.corrupt_rate, duplicate_rate=fl.duplicate_rate,
        max_retries=fl.max_retries, backoff_base=fl.backoff_base,
        backoff_factor=fl.backoff_factor, reorder_jitter=fl.reorder_jitter,
        quarantine_after=fl.quarantine_after,
        quarantine_rounds=fl.quarantine_rounds,
        corrupt_mode=fl.corrupt_mode, seed=seed)


def _privacy_config(spec: ExperimentSpec):
    """[privacy] -> PrivacyConfig, or None when the section is inert (no
    noise budget and no secure aggregation: the inert spec builds the
    exact pre-privacy sim, golden-pinned)."""
    pv = spec.privacy
    if not (pv.eps > 0 or pv.secure_agg):
        return None
    from repro.privacy import PrivacyConfig
    # the server XORs this with its own privacy tag (0x9D1A) to key the
    # noise stream, decorrelating it from the arrival and codec RNGs, so
    # the experiment seed passes through plain here
    seed = pv.seed if pv.seed is not None else spec.seed
    return PrivacyConfig(
        mechanism=pv.mechanism, eps=pv.eps, delta=pv.delta,
        sensitivity=pv.sensitivity, clip=pv.clip,
        secure_agg=pv.secure_agg, mask_bytes=pv.mask_bytes, seed=seed)


def build(spec: ExperimentSpec) -> "RunHandle":
    """Materialize a validated spec into a RunHandle."""
    data = task_data(spec)
    alg_entry = registry.ALGORITHMS[spec.algorithm.name]
    cfg, state = alg_entry.build(spec.algorithm, spec.task.m, data.params0,
                                 jax.random.PRNGKey(spec.seed))
    fleet_seed = spec.fleet.seed if spec.fleet.seed is not None \
        else spec.seed
    profiles = registry.FLEETS[spec.fleet.kind].build(
        spec.fleet, spec.task.m, fleet_seed)
    telemetry = None
    if spec.telemetry.enabled:
        from repro.telemetry import EventRecorder
        telemetry = EventRecorder()
    sim = FedSim(alg=alg_entry.sim_alg, cfg=cfg, state=state,
                 batches=data.batches, loss_fn=data.loss_fn,
                 profiles=profiles, sim=_sim_config(spec),
                 telemetry=telemetry)
    return RunHandle(spec=spec, sim=sim, data=data)


@dataclasses.dataclass
class RunHandle:
    """A built experiment: the FedSim plus the task-aware helpers every
    driver (CLI, train launcher, benchmarks) needs around it."""

    spec: ExperimentSpec
    sim: FedSim
    data: registry.TaskData

    def __post_init__(self):
        loss, batches = self.data.loss_fn, self.data.batches
        key = (loss, id(batches))
        # cap matches _TASK_CACHE's intent (3 entries per task): these
        # closures pin the task's device batches, so a larger bound would
        # keep evicted tasks' datasets alive behind the task memo's back
        self._fobj = fifo_cache_get(
            _OBJ_CACHE, ("fobj", *key),
            lambda: jax.jit(
                lambda w: fedepm.global_objective(loss, w, batches)),
            cap=24)
        # f at every row of a (rounds, d) broadcast stream. lax.map, not
        # vmap: each row runs _fobj's own matvec, so the values are _fobj's
        # bit for bit (vmap turns the matvecs into one matmul, which the
        # TPU may round through bfloat16 at default precision)
        self._fobjs = fifo_cache_get(
            _OBJ_CACHE, ("fobjs", *key),
            lambda: jax.jit(lambda ws: jax.lax.map(
                lambda w: fedepm.global_objective(loss, w, batches), ws)),
            cap=24)
        self._gsq = fifo_cache_get(
            _OBJ_CACHE, ("gsq", *key),
            lambda: jax.jit(
                lambda w: fedepm.global_grad_sq_norm(loss, w, batches)),
            cap=24)
        # per-round broadcast points can be stacked/tracked only when the
        # parameter pytree is one flat vector (the logreg task); LM pytrees
        # are evaluated at chunk boundaries instead
        self._w_stackable = isinstance(self.data.params0, jax.Array)

    # -- task-aware helpers --------------------------------------------------

    def objective(self, w) -> jax.Array:
        """f(w) = sum_i f_i(w) over the spec task's client batches."""
        return self._fobj(w)

    def grad_sq_norm(self, w) -> jax.Array:
        """||grad f(w)||^2 (the termination rule's input)."""
        return self._gsq(w)

    def accuracy(self) -> float | None:
        """Task accuracy at the current broadcast point (logreg only)."""
        if not self.data.supports_accuracy:
            return None
        from repro.core.tasks import accuracy_logistic
        self.sim.host_syncs += 1
        return float(accuracy_logistic(
            self.sim.state.w_tau, jnp.asarray(self.data.aux["X"]),
            jnp.asarray(self.data.aux["y"])))

    def _read_objective(self, w, round_idx: int, *,
                        span: str = "repro.run.objective") -> float:
        """f(w) on the host: one blocking device-to-host transfer, counted
        in ``sim.host_syncs`` and named in a profiler trace. The eager
        engine reads once a round, the summary once at the end."""
        self.sim.host_syncs += 1
        with TraceAnnotation(span, round=round_idx):
            return float(self._fobj(w))

    def _read_objectives(self, ws: np.ndarray, round0: int) -> list[float]:
        """f at each row of a scan chunk's (rounds, d) broadcast stream:
        one upload, one program and one blocking transfer for the chunk,
        counted once in ``sim.host_syncs``; the span's ``round`` is the
        chunk's first round."""
        self.sim.host_syncs += 1
        with TraceAnnotation("repro.run.objective", round=round0):
            return np.asarray(self._fobjs(jnp.asarray(ws))).tolist()

    # -- the execution loop --------------------------------------------------

    def _terminated(self, f_hist: list, *, w, metrics) -> bool:
        # the paper's variance criterion fires spuriously on a flat start
        # (abandoned rounds leave f_hist at f(w0)): require history AND at
        # least one aggregated round before trusting it. ``w``/``metrics``
        # are the broadcast point and SimMetrics prefix AS OF the round
        # being tested, so the scan engine can evaluate the rule
        # mid-chunk with exactly the eager loop's inputs.
        if not self.spec.engine.terminate or len(f_hist) < 8:
            return False
        if not any(not mm.abandoned for mm in metrics):
            return False
        from repro.configs.paper_logreg import termination_reached
        self.sim.host_syncs += 1
        return termination_reached(
            f_hist, float(self._gsq(w)), self.data.n_features)

    def run(self, report: Callable | None = None) -> dict:
        """Execute the spec's engine for its round budget -> summary dict.

        ``report(metrics, f)`` is called once per round with that round's
        SimMetrics and the objective at its broadcast point (None when the
        engine cannot track per-round objectives, i.e. scan/async over an
        LM parameter pytree). The summary is the simulate CLI's historical
        schema -- alg/policy/engine/latency, rounds, f_final, accuracy,
        simulated time, straggler/byte ledger totals, and the staleness
        stats under the async policy. With ``spec.telemetry.enabled`` the
        summary additionally carries a ``"telemetry"`` block (metric
        snapshot + series, repro.telemetry.sinks.telemetry_summary) and the
        configured sinks are written at run end; a telemetry-off summary
        is byte-identical to previous releases.

        The eager engine reads f once a round. The scan engine reads a
        chunk's per-round values in one batched program and one transfer,
        then reports them, tests termination and rolls back round by
        round, as the eager loop does.
        """
        eng = self.spec.engine
        entry = registry.ENGINES[eng.name]
        if entry.runner is not None:     # registered extension engine
            return entry.runner(self, report)
        sim = self.sim
        tel = self.spec.telemetry
        f_hist: list[float] = []
        rounds_run = 0
        wall0 = time.perf_counter() if tel.enabled else None
        with contextlib.ExitStack() as stack:
            if tel.enabled and tel.jax_profiler_dir:
                from repro.telemetry import jax_profile
                stack.enter_context(jax_profile(tel.jax_profiler_dir))
            if eng.name == "eager":
                for _ in range(eng.rounds):
                    met = sim.step()
                    rounds_run += 1
                    f_hist.append(self._read_objective(
                        sim.state.w_tau, sim.round_idx - 1))
                    if report is not None:
                        report(met, f_hist[-1])
                    if self._terminated(f_hist, w=sim.state.w_tau,
                                        metrics=sim.metrics):
                        break
            else:                        # scan: fused multi-round chunks
                collect = self._w_stackable
                chunk = eng.chunk if eng.chunk is not None \
                    else (8 if eng.terminate else eng.rounds)
                check = eng.terminate and collect
                stopped = False
                while rounds_run < eng.rounds and not stopped:
                    todo = min(chunk, eng.rounds - rounds_run)
                    # --terminate parity: snapshot before the chunk so an
                    # overshooting chunk can roll back (state, RNG, clock,
                    # ledger, telemetry) and re-run exactly the rounds the
                    # eager loop would have -- the stopping round is
                    # decided from the chunk's per-round broadcast stream
                    snap = sim.snapshot() if check else None
                    r0 = sim.round_idx
                    res = run_rounds(sim, todo, collect_w_tau=collect,
                                     mesh=eng.mesh,
                                     event_table_capacity=(
                                         eng.event_table_capacity))
                    if collect:
                        fs = self._read_objectives(res.w_tau, r0)
                        for i, (met, f, w) in enumerate(
                                zip(res.metrics, fs, res.w_tau)):
                            f_hist.append(f)
                            if report is not None:
                                report(met, f)
                            if check and self._terminated(
                                    f_hist, w=w,
                                    metrics=sim.metrics[:rounds_run + i
                                                        + 1]):
                                keep = i + 1
                                if keep < todo:
                                    sim.restore(snap)
                                    run_rounds(
                                        sim, keep, collect_w_tau=False,
                                        mesh=eng.mesh,
                                        event_table_capacity=(
                                            eng.event_table_capacity))
                                rounds_run += keep
                                stopped = True
                                break
                    else:
                        for met in res.metrics:
                            if report is not None:
                                report(met, None)
                    if not stopped:
                        rounds_run += todo
        summary = self._summary(f_hist, rounds_run)
        if tel.enabled:
            from repro.telemetry import (telemetry_summary,
                                         write_events_jsonl, write_trace)
            recorder = sim.telemetry
            summary["telemetry"] = telemetry_summary(
                recorder, objective=f_hist, rounds=rounds_run,
                wall_s=time.perf_counter() - wall0,
                host_syncs=sim.host_syncs)
            if tel.events_jsonl:
                write_events_jsonl(recorder.events, tel.events_jsonl)
            if tel.trace_out:
                write_trace(recorder.events, tel.trace_out,
                            label=self.spec.name)
        return summary

    def _summary(self, f_hist: list, rounds_run: int) -> dict:
        sim, spec = self.sim, self.spec
        f_final = f_hist[-1] if f_hist else self._read_objective(
            sim.state.w_tau, sim.round_idx - 1,
            span="repro.run.final_objective")
        summary = {
            "spec_name": spec.name,
            "alg": spec.algorithm.name, "policy": spec.policy.name,
            "engine": spec.engine.name, "latency": spec.fleet.latency,
            "rounds": rounds_run, "f_final": f_final / spec.task.m,
            "accuracy": self.accuracy(), "sim_time_s": sim.t,
            "stragglers_dropped": sum(mm.n_dropped for mm in sim.metrics),
            "abandoned_rounds": sum(mm.abandoned for mm in sim.metrics),
            "bytes_up": sim.ledger.total_up,
            "bytes_down": sim.ledger.total_down,
            "bytes_total": sim.ledger.total,
            "up_bytes_per_client_round": sim.up_bytes_per_client,
        }
        if spec.policy.name == "async":
            summary["staleness_max"] = max(
                (mm.staleness_max for mm in sim.metrics), default=0)
            summary["staleness_mean"] = float(np.mean(
                [mm.staleness_mean for mm in sim.metrics
                 if not mm.abandoned] or [0.0]))
        if sim._faults is not None:
            summary["faults"] = sim._faults.summary()
        if sim._privacy is not None:
            summary["privacy"] = sim._privacy.summary()
        return summary
