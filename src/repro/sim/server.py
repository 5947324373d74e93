"""Event-driven federated server simulation: aggregation over simulated time.

Wraps the UNMODIFIED round functions (core.fedepm.fedepm_round and the
core.baselines rounds) in a client/server timing model: each round the
server contacts a candidate set, clients.round_arrivals draws per-client
completion times from the device profiles, and an aggregation POLICY turns
arrivals into (participation mask, simulated round duration):

  sync        -- wait for every contacted available client; round time is
                 the slowest arrival (stragglers gate the round).
  deadline    -- drop candidates past a wall-clock cutoff; dropped clients
                 carry state through exactly as the paper's eq. (22)
                 non-selected clients do (the mask hook reuses the same
                 tree_where_client carry path). Round time is the deadline
                 when anyone misses it, else the slowest arrival.
  adaptive    -- per-client deadlines learned online: an EWMA of observed
                 report latencies (clients.AdaptiveDeadlines) budgets each
                 round's wait for client i at slack*ewma_i; never-observed
                 clients get an infinite budget, so round 1 degrades to
                 sync and the cutoffs tighten as evidence arrives. Dropped
                 clients carry through via eq. (22) as under ``deadline``.
  overselect  -- contact a uniform candidate set drawn at rate rho*factor
                 (the sampler's |S| = round(rho*factor*m) convention),
                 aggregate the first ceil(rho*m) arrivals; round time is
                 the last kept arrival.
  async       -- FedBuff-style buffered asynchrony; see below.

Asynchronous client-level dispatch (policy="async")
---------------------------------------------------
The server no longer runs in rounds, and -- since the client-level
refactor -- no longer dispatches in cohort lockstep either. It keeps ONE
time-ordered event queue of per-client events:

  start  -- client i receives the broadcast and begins local work. Fires
            only while the number of in-flight clients is below
            ``max_concurrency`` (0 = unlimited); slot-blocked starts wait
            in a FIFO and fire the moment an upload frees a slot.
  upload -- client i's contribution arrives at the server and is appended
            to the aggregation buffer.

Selection stays on the SAME key stream as sync: whenever the system runs
below one cohort of work (at step entry, or when the queue drains
mid-fill) the server draws the next cohort mask and queues one start
event per live member; unreachable members cost their broadcast bytes
immediately and never occupy a slot. Start events that fire at the same
instant batch into one round-function call (one key advance), so an
uncapped server dispatches whole cohorts exactly like the old cohort
engine, while a capped server trickles the cohort out client by client --
each later client trains on the broadcast CURRENT at its own start time,
not the one its cohort-mates saw.

An aggregation applies once ``buffer_size`` contributions are in. Clients
therefore train on STALE broadcasts: a contribution dispatched at server
version v and merged at version v' has staleness s = v' - v and is folded
into the server's Z with weight gamma = (1 + s)^(-staleness_exp)
(participation.staleness_weight, the FedBuff convention), i.e.
Z_i <- gamma * z_i + (1 - gamma) * Z_i. One ``step()`` is one aggregation
event. For the BASELINE algorithms each dispatch group additionally
anchors its broadcast on the live membership of the newest cohort draw
(``agg_mask`` hook in core/baselines.py): eq. (34)'s selected-mean then
averages over the whole cohort's latest uploads instead of degenerating
to a one-client mean when the concurrency cap splits a cohort.

With max_concurrency >= cohort, buffer_size = cohort, full availability
and no codec, every start fires instantly, every contribution merges at
staleness 0 (gamma = 1 exactly), and the event sequence degenerates to
dispatch -> drain -> merge -> dispatch: the trajectory is BIT-FOR-BIT the
synchronous one (tests/test_sim_async.py, for FedEPM and the baselines).
A cohort draw that is entirely offline leaves the algorithm state
(including the key) untouched, exactly like an abandoned sync round; after
_MAX_DRY_DISPATCHES consecutive such draws the step gives up and reports
abandoned=True.

Both engines run this SAME event loop. All device work routes through a
three-method executor seam (draw_candidates / fire / merge): the eager
executor below performs it at each event, while the scan engine
(repro.sim.engine) swaps in a recording executor that defers fires and
merges into an op program one compiled ``lax.scan`` replays over a
fixed-capacity payload table. Every host-side quantity -- clock, metrics,
ledger, staleness, telemetry -- is computed by identical pump code either
way, which is what makes scan async bit-for-bit comparable to eager
(tests/test_engine_async.py).

The mask is fed into the round via ``fedepm_round(..., mask=...)`` -- the
selection key stream is unchanged, so with policy="sync", full availability,
deterministic latency and no codec the simulated trajectory is BIT-FOR-BIT
the one core.fedepm produces on its own (tests/test_sim.py asserts this).

A round in which no candidate reports before the cutoff is ABANDONED: the
algorithm state is untouched (no key advance -- the server never aggregated),
the wasted broadcast bytes are still charged, and simulated time advances to
the deadline-policy cutoff, matching min-report-count behaviour of
production FL servers. (A sync round with every contacted client offline
has no cutoff to wait for and costs zero simulated time.)

Fault injection (SimConfig.faults, repro.sim.faults)
----------------------------------------------------
With a ``FaultConfig`` attached the server consults a seeded
``FaultModel`` at its arrival points and runs the defenses in the shared
host code: quarantined clients are removed from the candidate set before
dispatch; each upload runs an attempt chain (mid-flight drop / transient
failure with retry + exponential backoff / corruption screened and
counted toward quarantine / clean delivery, possibly duplicated and
deduped); every fired attempt is billed to the byte ledger via the count
path. A round that loses its whole cohort to faults is abandoned exactly
like a deadline miss. All decisions are host-side and replayed
identically by the scan engine, so fault-injected runs stay bit-for-bit
across engines; ``faults=None`` (any zero-rate config) leaves every path
above byte-identical to the fault-free simulator.

Upload privacy (SimConfig.privacy, repro.privacy)
-------------------------------------------------
With a ``PrivacyConfig`` attached the upload path runs through
``transport.private_roundtrip`` (clip + calibrated DP noise in front of
the codec, fused into one kernel launch on the dense quantized Laplace
configuration), a host-side per-client accountant charges ``eps`` for
every MERGED contribution (``privacy_charge`` telemetry events), and --
with secure aggregation on -- every upload attempt that reaches the wire
carries ``mask_bytes`` of pairwise-mask exchange, folded into the
per-upload wire size so the ByteLedger bills masks under exactly the
same rule as payloads (clean arrivals + retries + discarded duplicates).
Noise is drawn from a dedicated privacy key stream
(``fold_in(privacy_key, round_idx)`` clocked, ``fold_in(privacy_key,
serial)`` async), so both engines reproduce every draw bit-for-bit;
``privacy=None`` (or any inert config) leaves every path above
byte-identical to the pre-privacy simulator.
"""
from __future__ import annotations

import collections
import copy
import dataclasses
import functools
import heapq
import math
from typing import Any, Callable, NamedTuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.profiler import TraceAnnotation

from repro.core import baselines, fedepm, participation
from repro.core.treeutil import tmap, tree_size, tree_where_client
from repro.privacy import PrivacyConfig, build_privacy_model
from repro.sim import clients as simclients
from repro.sim.faults import FaultConfig, FaultRoundOutcome, build_fault_model
from repro.sim.transport import (
    ByteLedger,
    CodecConfig,
    codec_event_attrs,
    codec_roundtrip,
    draw_unit_noise,
    ef_roundtrip,
    encoded_client_bytes,
    private_ef_roundtrip,
    private_roundtrip,
    tree_client_bytes,
)
from repro.telemetry.events import NULL_RECORDER

_POLICIES = ("sync", "deadline", "adaptive", "overselect", "async")

# async: consecutive all-offline cohort draws before a step gives up
_MAX_DRY_DISPATCHES = 3

# fault injection only: in-loop cohort draws one aggregation event may
# make before it stops waiting for a full buffer and merges what it has.
# Under heavy loss every draw can come up live-but-lost -- the dry counter
# above never trips (the cohorts ARE live) yet the buffer never fills, so
# without this cap a drop_rate=1.0 run would pump forever.
_MAX_FAULT_SELECTS = 8

# event-queue kinds (heap entries sort by (time, push sequence, kind))
_EV_START = 0    # payload: (client index, round-trip duration seconds)
_EV_UPLOAD = 1   # payload: _Contribution


@dataclasses.dataclass(frozen=True)
class SimConfig:
    policy: str = "sync"            # one of _POLICIES
    deadline: float = math.inf      # seconds, deadline policy cutoff
    overselect_factor: float = 1.5  # candidate draw rate = rho * factor
    latency: str = "deterministic"  # clients.make_latency_model kind
    latency_sigma: float = 0.5
    latency_alpha: float = 1.2
    seed: int = 0
    codec: CodecConfig | None = None
    # async (buffered) aggregation
    buffer_size: int = 0            # contributions per aggregation; 0 = cohort
    staleness_exp: float = 0.5      # gamma = (1 + staleness)^-exp
    max_concurrency: int = 0        # async: in-flight client cap; 0 = no cap
    # adaptive per-client deadlines
    deadline_slack: float = 2.0     # wait budget = slack * ewma_i
    ewma_beta: float = 0.3          # EWMA weight of the newest observation
    # fault injection (repro.sim.faults); None = the fault-free simulator
    faults: FaultConfig | None = None
    # upload privacy (repro.privacy); None = the pre-privacy simulator
    privacy: PrivacyConfig | None = None


class SimMetrics(NamedTuple):
    round_idx: int
    t_round: float       # simulated duration of this round (s)
    t_total: float       # cumulative simulated wall-clock (s)
    n_contacted: int     # candidates the server broadcast to
    n_aggregated: int    # uploads that made it into the aggregate
    n_dropped: int       # contacted but not aggregated (stragglers/offline)
    bytes_down: float
    bytes_up: float
    abandoned: bool      # nobody reported before the cutoff
    staleness_mean: float = 0.0  # async: mean versions-behind of the merge
    staleness_max: int = 0       # async: worst versions-behind of the merge


def make_sim_metrics(*, round_idx: int, t_round: float, t_total: float,
                     n_contacted: int, n_aggregated: int, brec: dict,
                     abandoned: bool, staleness=(),
                     n_dropped: int | None = None) -> SimMetrics:
    """The ONE SimMetrics constructor both engines use.

    The eager server and the scan engine's host bookkeeping loop build
    their per-round metrics through this helper, so the two paths cannot
    drift apart field-by-field (tests/test_engine.py pins schema equality).
    ``brec`` is the ByteLedger record of the round; ``staleness`` the
    per-merged-contribution versions-behind sequence (clocked rounds merge
    at staleness 0 and pass the default).
    """
    staleness = list(staleness)
    return SimMetrics(
        round_idx=round_idx, t_round=t_round, t_total=t_total,
        n_contacted=int(n_contacted), n_aggregated=int(n_aggregated),
        n_dropped=int(n_contacted) - int(n_aggregated)
        if n_dropped is None else int(n_dropped),
        bytes_down=brec["down"], bytes_up=brec["up"],
        abandoned=bool(abandoned),
        staleness_mean=float(np.mean(staleness)) if staleness else 0.0,
        staleness_max=int(max(staleness)) if staleness else 0)


def emit_clocked_round_events(rec, *, policy: str, round_idx: int,
                              t0: float, candidates: np.ndarray,
                              arrivals: np.ndarray, mask: np.ndarray,
                              dur: float, rec_up: np.ndarray,
                              abandoned: bool,
                              codec: CodecConfig | None,
                              up_bytes: float,
                              faults: "FaultRoundOutcome | None" = None
                              ) -> None:
    """Emit one clocked round's telemetry events (sync/deadline/adaptive/
    overselect; policy="async" has its own event-loop instrumentation).

    Called with the round's already-computed host arrays by BOTH the eager
    server and the scan engine's bookkeeping loop -- the same inputs
    produce the same stream, which is what makes eager and scan runs
    comparable event-for-event (tests/test_telemetry.py pins it).
    Timestamps: dispatches at the round's start ``t0``, each upload at
    ``t0 + min(arrival, dur)`` (a straggler's upload is cut at the round
    end), merge/abandon at ``t0 + dur``.
    """
    rec.event("round_start", ts=t0, round_idx=round_idx, policy=policy)
    for i in np.flatnonzero(candidates):
        a = float(arrivals[i])
        if math.isfinite(a):
            rec.event("dispatch", ts=t0, round_idx=round_idx, client=int(i),
                      arrival_s=a)
        else:
            rec.event("dispatch", ts=t0, round_idx=round_idx, client=int(i),
                      live=False)
    for i in np.flatnonzero(rec_up):
        rec.event("upload_arrival", ts=t0 + min(float(arrivals[i]), dur),
                  round_idx=round_idx, client=int(i))
    t_end = t0 + dur
    if faults is not None:
        # fault resolution happened DURING the round: events carry the
        # attempt-chain times relative to the round start (a lost upload's
        # timestamp may exceed ``dur`` -- the server had already moved on)
        for cl, t_ev, att in faults.retries:
            rec.event("retry", ts=t0 + t_ev, round_idx=round_idx,
                      client=cl, attempt=att)
        for cl, t_ev, reason in faults.drops:
            rec.event("upload_drop", ts=t0 + t_ev, round_idx=round_idx,
                      client=cl, reason=reason)
        for cl, t_ev in faults.duplicates:
            rec.event("duplicate_discard", ts=t0 + t_ev,
                      round_idx=round_idx, client=cl)
        for cl, until in faults.quarantines:
            rec.event("quarantine", ts=t_end, round_idx=round_idx,
                      client=cl, until_round=until)
    if abandoned:
        rec.event("abandon", ts=t_end, round_idx=round_idx,
                  n_contacted=int(candidates.sum()))
        return
    n_agg = int(mask.sum())
    if codec is not None and n_agg:
        rec.event("codec_encode", ts=t_end, round_idx=round_idx,
                  **codec_event_attrs(codec, n_clients=n_agg,
                                      up_bytes=up_bytes))
    rec.event("merge", ts=t_end, round_idx=round_idx, n=n_agg, t_round=dur)


def apply_clocked_privacy(privacy, rec, *, round_idx: int, t_end: float,
                          mask: np.ndarray, rec_up: np.ndarray,
                          faults: "FaultRoundOutcome | None" = None) -> None:
    """One clocked round's privacy bookkeeping (accountant + mask billing).

    Shared by the eager server and the scan engine's host loop, called
    right after ``emit_clocked_round_events`` with the same host arrays,
    so accountant totals and the ``privacy_charge``/``mask_exchange``
    event stream are identical between engines. ``privacy`` is the
    ``PrivacyModel`` (None = no-op). Mask attempts equal the round's
    billed upload count -- delivered uploads plus every fault attempt
    that reached the wire -- which is exactly what the ByteLedger's count
    path charges, so mask bytes and ledger bytes cannot drift. Charges
    apply to MERGED clients only (the mask), never to stragglers or
    fault-lost uploads: their noisy payloads were never consumed.
    """
    if privacy is None:
        return
    cfg = privacy.cfg
    attempts = int(np.asarray(rec_up).sum())
    if faults is not None:
        attempts += int(faults.extra_up.sum())
    mbytes = privacy.bill_masks(attempts)
    if cfg.secure_agg and attempts and rec.enabled:
        rec.event("mask_exchange", ts=t_end, round_idx=round_idx,
                  attempts=attempts, bytes=mbytes)
    if cfg.eps > 0:
        for i in np.flatnonzero(np.asarray(mask)):
            tot = privacy.charge(int(i))
            if rec.enabled:
                rec.event("privacy_charge", ts=t_end, round_idx=round_idx,
                          client=int(i), eps=cfg.eps, eps_total=tot)


@dataclasses.dataclass
class _Contribution:
    """One in-flight client upload (async policy).

    The dispatch group's uploaded rows are gathered into ONE stacked batch
    per group (``_fire_group``); each contribution references its row of
    that shared batch instead of holding a privately sliced (1, ...) copy,
    so a g-client dispatch costs one gather per leaf, not 2g slice ops.

    Under the scan engine (repro.sim.engine) the batch is the engine's
    fixed-capacity payload TABLE instead of a per-group gather: ``slot`` is
    the table row holding this upload, ``z_batch``/``w_batch`` point at the
    table trees once the recording chunk has been replayed (None while the
    upload only exists as a recorded fire op). A table IS a batch, so a
    later eager ``step()`` merges a table-backed contribution through the
    exact same ``merge_contribution`` path.
    """

    client: int
    version: int   # server version at dispatch (staleness anchor)
    serial: int    # global upload serial (codec dither stream)
    z_batch: Any   # (g_pad, ...) stacked upload rows of the dispatch group
    w_batch: Any   # (g_pad, ...) stacked iterate rows of the dispatch group
    row: int       # this client's row within the batch
    slot: int = -1  # scan engine: payload-table row (-1 = eager batch mode)
    attempt: int = 1  # fault injection: delivery attempt (1 = original)
    dup: bool = False  # fault injection: duplicate ghost (never merged;
    #                    carries no batch refs and owns no table slot)


@jax.named_scope("merge")
def merge_contribution(Z, W, H, z_batch, w_batch, batch_row, idx, gamma,
                       key, noise, *, codec: CodecConfig | None, ef: bool,
                       privacy: PrivacyConfig | None = None):
    """Fold one arrived upload into the server's stacked state (PURE).

    The ONE merge/staleness function both engines call: the eager event
    loop dispatches it through the jitted ``_merge_contribution`` wrapper
    below, and the scan engine (repro.sim.engine) traces it directly inside
    its compiled async chunk with the payload table as the batch -- one
    definition, so the two paths cannot drift.

    ``batch_row`` selects the contribution's row out of its dispatch
    group's shared (g_pad, ...) batch (a dynamic slice, so one compiled
    program serves every row; group batches are padded to power-of-two
    sizes, bounding recompiles to log2 of the cohort). The upload is
    decoded first (codec memoryless fallback = the server's CURRENT stale
    row; with error feedback the shared memory row in H), then
    staleness-merged: Z_i <- gamma * z_hat + (1 - gamma) * Z_i. The
    gamma >= 1 branch replaces the row EXACTLY (no arithmetic), which is
    what makes the zero-staleness trajectory bit-identical to sync. W_i is
    replaced outright -- it is the client's own iterate, which the client
    reports authoritatively; only the aggregate-facing Z is down-weighted.

    With a noisy ``privacy`` config the decode runs through the private
    round-trips instead (clip + DP noise in front of the codec); ``noise``
    is the contribution's (1, ...) unit-noise tree, host-drawn from the
    privacy stream folded on the upload serial
    (``transport.draw_unit_noise`` -- data, so eager and scan consume
    bit-identical draws). Privacy None (or eps == 0) reduces every branch
    to the historical path bit-for-bit and ``noise`` is unused (callers
    pass None).
    """
    def row(tree):
        return tmap(
            lambda x: jax.lax.dynamic_slice_in_dim(x, idx, 1, axis=0), tree)

    def batch(tree):
        return tmap(
            lambda x: jax.lax.dynamic_slice_in_dim(x, batch_row, 1, axis=0),
            tree)

    def set_row(tree, r):
        return tmap(
            lambda x, rr: jax.lax.dynamic_update_slice_in_dim(
                x, rr.astype(x.dtype), idx, axis=0), tree, r)

    z_row = batch(z_batch)
    w_row = batch(w_batch)

    noisy = privacy is not None and privacy.eps > 0
    if codec is None and not noisy:
        z_hat = z_row
        H_new = H
    elif ef:
        z_hat = (private_ef_roundtrip(z_row, row(H), key, noise, codec,
                                      privacy) if noisy
                 else ef_roundtrip(z_row, row(H), key, codec))
        H_new = set_row(H, z_hat)
    else:
        z_hat = (private_roundtrip(z_row, row(Z), key, noise, codec, privacy)
                 if noisy
                 else codec_roundtrip(z_row, row(Z), key, codec))
        H_new = H

    def zmerge(zl, r):
        cur = jax.lax.dynamic_slice_in_dim(zl, idx, 1, axis=0)
        new = jnp.where(gamma >= 1.0, r, gamma * r + (1.0 - gamma) * cur)
        return jax.lax.dynamic_update_slice_in_dim(
            zl, new.astype(zl.dtype), idx, axis=0)

    return tmap(zmerge, Z, z_hat), set_row(W, w_row), H_new


#: jitted entry point of :func:`merge_contribution` (the eager path)
_merge_contribution = functools.partial(
    jax.jit, static_argnames=("codec", "ef", "privacy"))(merge_contribution)


def copy_tree(tree):
    """Fresh device copies of every leaf (donation/snapshot safety)."""
    return tmap(lambda x: jnp.array(x, copy=True), tree)


def client_work_flops(alg: str, *, k0: int, n_params: int, d_local: float,
                      prox_ell: int = 3) -> float:
    """Rough per-round client compute model (flops), for arrival times only.

    One loss gradient over d_local samples of an n_params model is ~4
    flops/sample/param (forward + backward matvec); FedEPM adds k0 cheap
    closed-form prox steps (~12 flops/param incl. the mu norm), the
    baselines re-evaluate the gradient every inner step (eqs. (35)/(36)).
    """
    grad = 4.0 * d_local * n_params
    if alg == "fedepm":
        return grad + k0 * 12.0 * n_params
    if alg == "sfedavg":
        return k0 * grad
    if alg == "sfedprox":
        return k0 * prox_ell * grad
    raise ValueError(f"unknown alg {alg!r}")


def _batches_d_local(batches) -> float:
    """Mean per-client sample count, from the validity mask when present."""
    if isinstance(batches, dict) and "mask" in batches:
        msk = np.asarray(batches["mask"])
        return float(msk.reshape(msk.shape[0], -1).sum(axis=1).mean())
    leaves = jax.tree_util.tree_leaves(batches)
    return float(leaves[0].shape[1]) if leaves and leaves[0].ndim > 1 else 1.0


_ALGS: dict[str, tuple[Callable, Callable]] = {
    "fedepm": (fedepm.fedepm_round, fedepm.default_round_mask),
    "sfedavg": (baselines.sfedavg_round, baselines.default_round_mask),
    "sfedprox": (baselines.sfedprox_round, baselines.default_round_mask),
}

# jitted-program cache shared ACROSS FedSim instances (bounded FIFO): a
# fresh per-instance ``jax.jit(lambda ...)`` re-traces on every
# construction, so benchmark/test code that builds many sims over the same
# (round fn, loss fn, config, batches) pays a full trace+compile per
# instance. Batches are keyed by identity; the cached closure keeps them
# alive, so the id cannot be recycled while the entry exists.
# ``fifo_cache_get`` is the one get-or-build-with-eviction helper; the
# engine's compiled-chunk caches (repro.sim.engine) use it too.
_JIT_CACHE: dict = {}


def fifo_cache_get(cache: dict, key, build: Callable, *, cap: int = 64):
    """Bounded memo: build-on-miss, FIFO eviction once ``cap`` is reached.

    Entries hold compiled closures that may pin device buffers (batches),
    so the bound is what keeps long sweeps over many tasks from leaking
    one dataset per cache entry.
    """
    fn = cache.get(key)
    if fn is None:
        if len(cache) >= cap:
            cache.pop(next(iter(cache)))
        fn = cache[key] = build()
    return fn


def _shared_jit(key, build: Callable):
    return fifo_cache_get(_JIT_CACHE, key, build)


class _EagerAsyncExec:
    """Device-work executor behind the async event loop (the reference).

    ``_pump_async`` is ONE scheduling implementation shared by both
    engines; everything that touches a jax array routes through this
    three-method seam. The eager executor performs the device work at the
    event, exactly as the pre-refactor event loop did. The scan engine
    (repro.sim.engine) swaps in a RECORDING executor that replays candidate
    draws from a precomputed key stream and defers fires/merges into a
    program one compiled ``lax.scan`` executes -- every host-side quantity
    (clock, metrics, ledger, telemetry, staleness) is computed by the same
    pump code either way, which is what makes the two engines comparable
    event-for-event.
    """

    recording = False

    def draw_candidates(self, sim) -> np.ndarray:
        cand = np.asarray(sim._candidates(sim.state))
        sim.host_syncs += 1
        return cand

    def fire(self, sim, group, mask: np.ndarray, contribs) -> None:
        """Run the round function for a dispatch group NOW; gather the
        group's upload/iterate rows into a shared batch and attach them to
        the group's contributions."""
        if sim._step_agg is not None:
            # baselines: anchor eq. (34)'s mean on the whole live cohort so
            # a capped sub-group dispatch still mixes across clients (the
            # uncapped group IS the cohort, recovering sync exactly). The
            # union with the group keeps the anchor non-empty even when a
            # NEWER cohort draw came up all-offline while this group sat
            # stalled (an empty mean would broadcast a zero vector).
            new_state, rmetrics = sim._step_agg(
                sim.state, sim._dev_mask(mask),
                sim._dev_mask(sim._cohort_live | mask))
        else:
            new_state, rmetrics = sim._step(sim.state, sim._dev_mask(mask))
        sim.state = sim.state._replace(
            w_tau=new_state.w_tau, k=new_state.k, key=new_state.key)
        sim.last_round_metrics = rmetrics
        # one gather per leaf for the whole group's upload/iterate rows
        # (vs 2 slice ops per CLIENT); indices pad to the next power of two
        # (repeating the last) so _merge_contribution compiles per pow2
        # bucket, not per group size
        idx = np.fromiter((i for i, _ in group), np.int64, len(group))
        pad = 1 << (len(group) - 1).bit_length() if len(group) > 1 else 1
        rows = jnp.asarray(np.concatenate(
            [idx, np.full(pad - len(group), idx[-1], np.int64)]))
        z_batch = tmap(lambda x: x[rows], new_state.Z)
        w_batch = tmap(lambda x: x[rows], new_state.W)
        for j, c in enumerate(contribs):
            c.z_batch, c.w_batch, c.row = z_batch, w_batch, j

    def merge(self, sim, c: "_Contribution", staleness: int,
              gamma: float) -> None:
        """Staleness-merge one arrived contribution into the server state."""
        key = jax.random.fold_in(sim._codec_key, c.serial)
        # the privacy stream folds on the same serial; the unit-noise
        # draw happens host-side in its own program (draw_unit_noise) so
        # the scan engine's replayed merges consume bit-identical noise
        noise = (draw_unit_noise(
            jax.random.fold_in(sim._privacy_key, c.serial),
            sim._noise_row_like, sim._privacy_tx)
            if sim._privacy_tx is not None else None)
        Z, W, H = _merge_contribution(
            sim.state.Z, sim.state.W, sim._H, c.z_batch, c.w_batch,
            jnp.asarray(c.row, jnp.int32),
            jnp.asarray(c.client, jnp.int32),
            jnp.asarray(gamma, jnp.float32), key, noise,
            codec=sim.sim.codec, ef=sim._ef, privacy=sim._privacy_tx)
        sim.state = sim.state._replace(Z=Z, W=W)
        sim._H = H
        if c.slot >= 0 and sim._async_table is not None:
            # table-backed contribution (dispatched under the scan engine,
            # merged eagerly): its payload slot is free again
            sim._async_table.free(c.slot)
            c.slot = -1

    def release(self, sim, c: "_Contribution") -> None:
        """Discard an in-flight contribution WITHOUT merging it (fault
        injection: the upload was lost or rejected) -- reclaim whatever
        payload storage it holds. Eager batch refs just drop with the
        contribution; table-backed slots are freed explicitly."""
        if c.slot >= 0 and sim._async_table is not None:
            sim._async_table.free(c.slot)
            c.slot = -1


#: shared stateless default executor (the eager reference semantics)
_EAGER_ASYNC_EXEC = _EagerAsyncExec()


class FedSim:
    """Drives one algorithm under one aggregation policy over simulated time.

    Parameters
    ----------
    alg : "fedepm" | "sfedavg" | "sfedprox"
    cfg : the algorithm's own config (FedEPMConfig / BaselineConfig) --
          the sim never alters it, so the math stays core/'s.
    state : initial algorithm state (init_state of the respective module).
    batches, loss_fn : as taken by the round functions.
    profiles : device heterogeneity (clients.make_profiles); default uniform.
    sim : SimConfig policy/latency/codec settings.
    work_flops : override the per-round client compute estimate.
    telemetry : an EventRecorder (repro.telemetry), or None for the shared
        no-op NULL_RECORDER. Recording is observational only -- it never
        draws RNG or dispatches jit work, so trajectories are bit-for-bit
        independent of it.
    """

    def __init__(self, *, alg: str, cfg: Any, state: Any, batches: Any,
                 loss_fn: Callable, profiles=None,
                 sim: SimConfig = SimConfig(),
                 work_flops: float | None = None, telemetry=None):
        if alg not in _ALGS:
            raise ValueError(f"unknown alg {alg!r}")
        if sim.policy not in _POLICIES:
            raise ValueError(
                f"unknown policy {sim.policy!r}; expected one of {_POLICIES}")
        if sim.buffer_size < 0:
            raise ValueError(f"buffer_size must be >= 0 (0 = cohort size); "
                             f"got {sim.buffer_size}")
        if sim.max_concurrency < 0:
            raise ValueError(f"max_concurrency must be >= 0 (0 = unlimited); "
                             f"got {sim.max_concurrency}")
        round_fn, mask_fn = _ALGS[alg]
        self.alg = alg
        self.cfg = cfg
        self.sim = sim
        self.state = state
        # raw round ingredients for the fused scan engine (repro.sim.engine
        # traces its own multi-round body over them) plus a device->host
        # transfer counter both engines report in BENCH_engine.json
        self._round_fn = round_fn
        self._batches = batches
        self._loss_fn = loss_fn
        self.host_syncs = 0
        self._mask_cache: dict[bytes, jax.Array] = {}
        self.profiles = profiles if profiles is not None \
            else simclients.uniform_profiles(cfg.m)
        if self.profiles.m != cfg.m:
            raise ValueError(
                f"profiles for m={self.profiles.m} but cfg.m={cfg.m}")
        self._latency = simclients.make_latency_model(
            sim.latency, sigma=sim.latency_sigma, alpha=sim.latency_alpha)
        self._rng = np.random.default_rng(sim.seed)
        self._codec_key = jax.random.PRNGKey(sim.seed ^ 0x5EED)
        # fault model on its OWN seeded stream -- never the arrival stream,
        # whose draw ORDER differs between engines (the scan engine batches
        # arrival draws per chunk); None whenever no fault process can fire
        self._faults = build_fault_model(sim.faults, cfg.m)
        # privacy accountant (None whenever the config is inert) and the
        # noise-transform config: eps == 0 privacy (secure-agg only) bills
        # masks but never perturbs values, so the transform -- a static
        # operand of the merge programs -- stays None and every device
        # path stays byte-identical to the pre-privacy simulator
        self._privacy = build_privacy_model(sim.privacy, cfg.m)
        self._privacy_tx = (sim.privacy if self._privacy is not None
                            and sim.privacy.eps > 0 else None)
        self._privacy_key = jax.random.PRNGKey(
            (sim.privacy.seed if sim.privacy is not None else 0) ^ 0x9D1A)
        # shape donor for per-contribution noise draws under the async
        # policy: one (1, ...) row per Z leaf (shapes only, never
        # materialized -- draw_unit_noise reads .shape)
        self._noise_row_like = (tmap(
            lambda x: jax.ShapeDtypeStruct((1,) + x.shape[1:], x.dtype),
            state.Z) if self._privacy_tx is not None else None)

        jit_key = (round_fn, loss_fn, cfg, id(batches))
        self._step = _shared_jit(
            ("step", *jit_key),
            lambda: jax.jit(
                lambda s, mask: round_fn(s, batches, loss_fn, cfg, mask)))
        # baselines accept a decoupled aggregation anchor (agg_mask) so the
        # async client-level scheduler can average eq. (34) over the whole
        # cohort while only a sub-group computes; fedepm's ENS already
        # aggregates every Z row, so no anchor is needed there
        if alg == "fedepm":
            self._step_agg = None
        else:
            self._step_agg = _shared_jit(
                ("step_agg", *jit_key),
                lambda: jax.jit(
                    lambda s, mask, agg: round_fn(s, batches, loss_fn, cfg,
                                                  mask, agg_mask=agg)))
        self._default_mask = _shared_jit(
            ("mask", mask_fn, cfg),
            lambda: jax.jit(lambda s: mask_fn(s, cfg)))
        if sim.policy == "overselect":
            # over-selection draws its own (bigger) uniform candidate set;
            # a coverage/full sampler's guarantee would be silently lost,
            # so refuse rather than mislead
            if getattr(cfg, "sampler", "uniform") != "uniform":
                raise ValueError(
                    "policy='overselect' only supports the uniform sampler; "
                    f"got cfg.sampler={cfg.sampler!r}")
            rho_eff = min(1.0, cfg.rho * sim.overselect_factor)

            def build_cand():
                def cand(s):
                    _, k_sel, _ = jax.random.split(s.key, 3)
                    return participation.sample_uniform(k_sel, cfg.m,
                                                        rho_eff)
                return jax.jit(cand)

            self._candidates = _shared_jit(
                ("cand_over", cfg.m, rho_eff), build_cand)
        else:
            self._candidates = self._default_mask
        self._n_keep = min(cfg.m, max(1, math.ceil(cfg.rho * cfg.m)))

        # byte model from the real state trees
        self._down_bytes = float(tree_client_bytes(state.w_tau))
        self._up_bytes = float(encoded_client_bytes(state.Z, sim.codec))
        if self._privacy is not None:
            # the pairwise-mask exchange rides every upload attempt:
            # folding it into the per-upload wire size makes the ledger
            # bill masks under exactly the PR 9 fault-billing rule (clean
            # arrivals + retries + duplicates) and slows the modeled
            # upload transfer accordingly; 0 when secure-agg is off
            self._up_bytes += self._privacy.mask_overhead
        self.telemetry = NULL_RECORDER if telemetry is None else telemetry
        self.ledger = ByteLedger(cfg.m, telemetry=self.telemetry)

        # error-feedback codec memory: the reconstruction h_i both sides
        # hold after client i's last DELIVERED upload (init: zeros, i.e.
        # the first upload is encoded in full against an empty memory)
        self._ef = sim.codec is not None and sim.codec.error_feedback
        self._H = tmap(jnp.zeros_like, state.Z) if self._ef else None

        if self._privacy_tx is not None:
            # noisy merge programs: the private round-trips in front of
            # (or instead of) the codec, keyed on (codec, privacy) so the
            # no-noise builders below keep their historical cache entries
            codec, privacy = sim.codec, self._privacy_tx
            if self._ef:

                def build_merge_ef_priv():
                    @jax.jit
                    def codec_merge_ef(z_new, H, z_prev, mask, key, noise):
                        dec = private_ef_roundtrip(z_new, H, key, noise,
                                                   codec, privacy)
                        return (tree_where_client(mask, dec, z_prev),
                                tree_where_client(mask, dec, H))
                    return codec_merge_ef

                self._codec_merge_ef = _shared_jit(
                    ("codec_merge_ef", codec, privacy), build_merge_ef_priv)
            else:

                def build_merge_priv():
                    @jax.jit
                    def codec_merge(z_new, z_prev, mask, key, noise):
                        z_dec = private_roundtrip(z_new, z_prev, key, noise,
                                                  codec, privacy)
                        return tree_where_client(mask, z_dec, z_prev)
                    return codec_merge

                self._codec_merge = _shared_jit(
                    ("codec_merge", codec, privacy), build_merge_priv)
        elif sim.codec is not None:
            codec = sim.codec
            if codec.error_feedback:

                def build_merge_ef():
                    @jax.jit
                    def codec_merge_ef(z_new, H, z_prev, mask, key):
                        dec = ef_roundtrip(z_new, H, key, codec)
                        return (tree_where_client(mask, dec, z_prev),
                                tree_where_client(mask, dec, H))
                    return codec_merge_ef

                self._codec_merge_ef = _shared_jit(
                    ("codec_merge_ef", codec), build_merge_ef)
            else:

                def build_merge():
                    @jax.jit
                    def codec_merge(z_new, z_prev, mask, key):
                        z_dec = codec_roundtrip(z_new, z_prev, key, codec)
                        return tree_where_client(mask, z_dec, z_prev)
                    return codec_merge

                self._codec_merge = _shared_jit(
                    ("codec_merge", codec), build_merge)

        if sim.policy == "adaptive":
            self.deadlines = simclients.AdaptiveDeadlines(
                cfg.m, beta=sim.ewma_beta, slack=sim.deadline_slack)

        if sim.policy == "async":
            # cohort size of the (uniform/full) selection stream; also the
            # in-system top-up target and the default buffer size
            self._cohort = max(
                1, int(np.asarray(self._default_mask(state)).sum()))
            self._buffer_k = sim.buffer_size or self._cohort
            self._max_conc = sim.max_concurrency or math.inf
            self._version = 0          # server model version (aggregations)
            self._serial = 0           # upload serial (codec dither stream)
            self._eseq = 0             # event push sequence (heap tie-break)
            self._events: list = []    # heap of (t, eseq, kind, payload)
            self._stalled: collections.deque = collections.deque()
            self._n_inflight = 0       # started clients awaiting arrival
            self._n_queued_starts = 0  # start events sitting in the heap
            self._cohort_live = np.zeros(cfg.m, bool)  # newest draw, live
            self._exec = _EAGER_ASYNC_EXEC  # device-work executor seam
            self._async_table = None   # scan engine's payload table

        self._work = work_flops if work_flops is not None else \
            client_work_flops(alg, k0=cfg.k0,
                              n_params=tree_size(state.w_tau),
                              d_local=_batches_d_local(batches))
        self.t = 0.0
        self.round_idx = 0
        self.metrics: list[SimMetrics] = []
        self.last_round_metrics = None  # algorithm RoundMetrics of last round

    def attach_telemetry(self, recorder) -> None:
        """Point the sim (and its byte ledger) at a telemetry recorder."""
        self.telemetry = recorder
        self.ledger.telemetry = recorder

    @property
    def up_bytes_per_client(self) -> float:
        """Encoded uplink wire bytes one client sends per round."""
        return self._up_bytes

    @property
    def down_bytes_per_client(self) -> float:
        """Dense broadcast wire bytes one contacted client receives."""
        return self._down_bytes

    def _dev_mask(self, mask: np.ndarray) -> jax.Array:
        """Device copy of a host boolean mask, cached by value.

        The async event path re-dispatches the same masks over and over
        (singleton groups under a concurrency cap, the live-cohort anchor
        between draws); uploading each occurrence anew costs one allocation
        + transfer per EVENT. The cache keys on the mask bytes, so each
        distinct mask is uploaded once per simulation (bounded FIFO, masks
        are m bools each).
        """
        key = mask.tobytes()
        buf = self._mask_cache.get(key)
        if buf is None:
            if len(self._mask_cache) >= 1024:
                self._mask_cache.pop(next(iter(self._mask_cache)))
            buf = jnp.asarray(mask)
            self._mask_cache[key] = buf
        return buf

    # -- policy -------------------------------------------------------------

    def _apply_policy(self, candidates: np.ndarray, arrivals: np.ndarray):
        """-> (mask (m,) bool, round duration seconds).

        Mask semantics live in core.participation (arrival_mask /
        first_arrivals_mask) so the jit-safe helpers and the sim cannot
        drift; only the round-duration bookkeeping is computed here.
        """
        pol = self.sim.policy
        self.host_syncs += 1  # each branch transfers one jit'd mask back
        cand_j = jnp.asarray(candidates)
        arr_j = jnp.asarray(arrivals)
        t_cand = np.where(candidates, arrivals, np.inf)
        if pol == "sync":
            # wait for every contacted client that is alive; an all-offline
            # round has no natural duration (sync has no cutoff) => 0.0
            mask = np.asarray(participation.arrival_mask(
                cand_j, arr_j, np.inf))
            dur = float(t_cand[mask].max()) if mask.any() else 0.0
            return mask, dur
        if pol == "deadline":
            dl = self.sim.deadline
            mask = np.asarray(participation.arrival_mask(cand_j, arr_j, dl))
            if not candidates.any():
                return mask, 0.0
            finite = t_cand[np.isfinite(t_cand)]
            if np.isfinite(t_cand[candidates]).all() \
                    and (t_cand[candidates] <= dl).all():
                return mask, float(t_cand[candidates].max())  # all beat it
            if np.isfinite(dl):                     # someone missed it
                return mask, float(dl)
            # infinite deadline but offline candidates: wait out the finite
            return mask, float(finite.max()) if finite.size else 0.0
        if pol == "adaptive":
            cut = self.deadlines.cutoffs()
            mask = np.asarray(participation.arrival_mask(
                cand_j, arr_j, jnp.asarray(cut)))
            # the server listens to candidate i until min(arrival_i, cut_i):
            # round time is the last moment it is still waiting for anyone
            wait = np.where(candidates, np.minimum(arrivals, cut), np.inf)
            finite = wait[np.isfinite(wait)]
            dur = float(finite.max()) if finite.size else 0.0
            self.deadlines.observe(candidates, arrivals)
            return mask, dur
        if pol == "overselect":
            mask = np.asarray(participation.first_arrivals_mask(
                cand_j, arr_j, self._n_keep))
            dur = float(t_cand[mask].max()) if mask.any() else 0.0
            return mask, dur
        raise ValueError(f"unknown policy {pol!r}")

    # -- one simulated round ------------------------------------------------

    def step(self) -> SimMetrics:
        with TraceAnnotation("repro.sim.step", round=self.round_idx):
            if self.sim.policy == "async":
                return self._step_async()
            return self._step_clocked()

    def _step_clocked(self) -> SimMetrics:
        candidates = np.asarray(self._candidates(self.state))
        self.host_syncs += 1
        arrivals = simclients.round_arrivals(
            self.profiles, self._rng, self._latency,
            work_flops=self._work, down_bytes=self._down_bytes,
            up_bytes=self._up_bytes)
        fo = None
        if self._faults is not None:
            # resolve fault chains BEFORE the policy: the policy then sees
            # the effective candidate set (quarantine removed) and arrival
            # times (retry-delayed / lost), so every defense downstream --
            # masking, abandonment, adaptive EWMA observation -- is the
            # existing code operating on what actually reached the server
            fo = self._faults.apply_clocked(
                round_idx=self.round_idx, candidates=candidates,
                arrivals=arrivals,
                cutoff=self.sim.deadline
                if self.sim.policy == "deadline" else math.inf)
            candidates, arrivals = fo.candidates, fo.arrivals
        mask, dur = self._apply_policy(candidates, arrivals)

        abandoned = candidates.any() and not mask.any()
        if abandoned:
            # server waited out the round (dur from the policy) and nobody
            # reported: algorithm state untouched, broadcast bytes spent
            rec_up = np.zeros(self.cfg.m, bool)
        else:
            prev_state = self.state
            new_state, rmetrics = self._step(
                self.state, jnp.asarray(mask))
            if self._privacy_tx is not None:
                key = jax.random.fold_in(self._codec_key, self.round_idx)
                # host-drawn unit noise, privacy stream folded on the
                # round index (the scan chunk feeds the SAME draws in as
                # xs, so the two engines perturb bit-identically)
                noise = draw_unit_noise(
                    jax.random.fold_in(self._privacy_key, self.round_idx),
                    prev_state.Z, self._privacy_tx)
                if self._ef:
                    Z_dec, self._H = self._codec_merge_ef(
                        new_state.Z, self._H, prev_state.Z,
                        jnp.asarray(mask), key, noise)
                    new_state = new_state._replace(Z=Z_dec)
                else:
                    new_state = new_state._replace(Z=self._codec_merge(
                        new_state.Z, prev_state.Z, jnp.asarray(mask), key,
                        noise))
            elif self.sim.codec is not None:
                key = jax.random.fold_in(self._codec_key, self.round_idx)
                if self._ef:
                    Z_dec, self._H = self._codec_merge_ef(
                        new_state.Z, self._H, prev_state.Z,
                        jnp.asarray(mask), key)
                    new_state = new_state._replace(Z=Z_dec)
                else:
                    new_state = new_state._replace(Z=self._codec_merge(
                        new_state.Z, prev_state.Z, jnp.asarray(mask), key))
            self.state = new_state
            self.last_round_metrics = rmetrics
            # uploads that completed within the round window (kept clients
            # plus over-selection ties); stragglers cut at the deadline
            # never finish their upload, offline clients never start one
            rec_up = np.asarray(candidates & np.isfinite(arrivals)
                                & (arrivals <= dur + 1e-12))
            if self.sim.policy == "adaptive":
                # per-client cutoffs: the server hangs up on client i at
                # cut_i, so only kept uploads were actually received
                rec_up = mask

        if self.telemetry.enabled:
            emit_clocked_round_events(
                self.telemetry, policy=self.sim.policy,
                round_idx=self.round_idx, t0=self.t, candidates=candidates,
                arrivals=arrivals, mask=mask, dur=dur, rec_up=rec_up,
                abandoned=bool(abandoned), codec=self.sim.codec,
                up_bytes=self._up_bytes, faults=fo)
        apply_clocked_privacy(
            self._privacy, self.telemetry, round_idx=self.round_idx,
            t_end=self.t + dur, mask=mask, rec_up=rec_up, faults=fo)
        if fo is None:
            brec = self.ledger.record_round(
                down_mask=candidates, up_mask=rec_up,
                down_bytes=self._down_bytes, up_bytes=self._up_bytes,
                ts=self.t + dur, round_idx=self.round_idx)
        else:
            # failed attempts and discarded duplicates sent real bytes:
            # bill them on top of the delivered-upload mask via the count
            # path (record_round is the counts==mask special case)
            brec = self.ledger.record_counts(
                down_counts=candidates.astype(np.int64),
                up_counts=rec_up.astype(np.int64) + fo.extra_up,
                down_bytes=self._down_bytes, up_bytes=self._up_bytes,
                ts=self.t + dur, round_idx=self.round_idx)
        self.t += dur
        m = make_sim_metrics(
            round_idx=self.round_idx, t_round=dur, t_total=self.t,
            n_contacted=int(candidates.sum()), n_aggregated=int(mask.sum()),
            brec=brec, abandoned=bool(abandoned))
        self.metrics.append(m)
        self.round_idx += 1
        return m

    # -- asynchronous client-level dispatch (policy="async") ----------------

    def _free_slots(self) -> float:
        return self._max_conc - self._n_inflight

    def _in_system(self) -> int:
        """Clients the server currently owes work to: in flight, stalled on
        a concurrency slot, or queued as unfired start events."""
        return self._n_inflight + len(self._stalled) + self._n_queued_starts

    def _select_cohort(self) -> int:
        """Draw the next cohort from the algorithm's key stream and queue
        one start event per LIVE member at the current simulated time.
        Returns the live count.

        Unreachable members cost their broadcast immediately (the contact
        RPC fails; a wasted broadcast, like an abandoned sync round) and
        never occupy a concurrency slot. The live mask is remembered as the
        aggregation anchor the baselines' agg_mask hook receives.
        """
        candidates = self._exec.draw_candidates(self)
        if self._faults is not None:
            # quarantined clients are not contacted at all: no broadcast
            # bytes, no slot, no dispatch event (the draw itself still
            # advances nothing -- selection is a pure key-stream read)
            candidates = candidates \
                & ~self._faults.quarantine_mask(self.round_idx)
        durations = simclients.round_arrivals(
            self.profiles, self._rng, self._latency,
            work_flops=self._work, down_bytes=self._down_bytes,
            up_bytes=self._up_bytes)
        live = candidates & np.isfinite(durations)
        self._cohort_live = live
        offline = candidates & ~live
        self._ev_contacted += int(offline.sum())
        self._ev_dropped += int(offline.sum())
        self._ev_down += offline.astype(np.int64)
        if self.telemetry.enabled:
            for i in np.flatnonzero(offline):
                self.telemetry.event("dispatch", ts=self.t,
                                     round_idx=self.round_idx,
                                     client=int(i), live=False)
        live_idx = np.flatnonzero(live)
        if live_idx.size:
            base = self._eseq
            entries = [(self.t, base + j, _EV_START,
                        (int(i), float(durations[i])))
                       for j, i in enumerate(live_idx)]
            # batched insert: extend + one O(n) heapify when the group is
            # a sizeable fraction of the heap; per-entry O(log n) pushes
            # when it is not (heapify re-sifts the WHOLE heap, a loss for
            # a singleton draw into a deep queue)
            n_heap = len(self._events)
            if live_idx.size * max(1, n_heap.bit_length()) >= n_heap:
                self._events.extend(entries)
                heapq.heapify(self._events)
            else:
                for e in entries:
                    heapq.heappush(self._events, e)
            self._eseq += int(live_idx.size)
            self._n_queued_starts += int(live_idx.size)
        return int(live_idx.size)

    def _fire_group(self, group: list[tuple[int, float]]) -> None:
        """Broadcast to ``group`` NOW: run the round function once over its
        members (clients compute against the broadcast they just received),
        which advances w_tau/k/key; the resulting W/Z rows only reach the
        server's state when their upload events are merged. Causality note:
        the broadcast aggregates state.Z, i.e. ONLY uploads already merged
        -- the group's own uploads live in the discarded new_state.Z until
        their arrivals merge, so no dispatch ever sees an in-flight upload.
        """
        mask = np.zeros(self.cfg.m, bool)
        mask[[i for i, _ in group]] = True
        self._ev_contacted += len(group)
        self._ev_down += mask.astype(np.int64)
        contribs = [
            _Contribution(client=i, version=self._version,
                          serial=self._serial + j, z_batch=None,
                          w_batch=None, row=j)
            for j, (i, _) in enumerate(group)]
        self._serial += len(group)
        # device work (round fn + row gather) routes through the executor:
        # the eager executor runs it now, the scan engine's recording
        # executor defers it into the compiled chunk program
        self._exec.fire(self, group, mask, contribs)
        self._n_inflight += len(group)
        if self.telemetry.enabled:
            for i, dur in group:
                self.telemetry.event(
                    "dispatch", ts=self.t, round_idx=self.round_idx,
                    client=int(i), dur_s=float(dur), version=self._version,
                    in_flight=self._n_inflight,
                    stalled=len(self._stalled))
        for (i, dur), c in zip(group, contribs):
            heapq.heappush(self._events,
                           (self.t + dur, self._eseq, _EV_UPLOAD, c))
            self._eseq += 1

    def _handle_faulty_upload(self, c: _Contribution) -> bool:
        """Resolve one popped upload event against the fault model.

        Returns True when the event was consumed here (lost, retried,
        rejected or deduped) and must NOT be buffered; False for a clean
        delivery the pump buffers as usual. Every attempt that reached the
        wire -- duplicates and rejected payloads included -- bills one
        upload to the count ledger. Runs identically under both engines
        (the pump is shared and the model's stream is its own), so the
        scan recording pass reproduces every decision made here.
        """
        fm = self._faults
        tel = self.telemetry.enabled
        if c.dup or (c.client, c.serial, c.attempt) in fm.seen:
            # duplicate delivery: billed, deduped on the (client, serial,
            # attempt) sequence number, never merged. Ghosts hold no batch
            # refs and never occupied a slot, so in-flight is untouched.
            # Counted here -- at discard/billing time -- so the counter
            # can never drift from the byte ledger (a ghost still queued
            # at run end is neither billed nor counted).
            self._ev_up[c.client] += 1
            fm.total_duplicates += 1
            if tel:
                self.telemetry.event(
                    "duplicate_discard", ts=self.t,
                    round_idx=self.round_idx, client=int(c.client))
            return True
        fate = fm.draw_outcome()
        if fate == "ok":
            delay = fm.draw_duplicate()
            if delay is not None:
                # the duplicate arrives reorder_jitter*U[0,1) late: a
                # payload-free ghost event dedup will discard on arrival
                ghost = dataclasses.replace(c, dup=True, slot=-1,
                                            z_batch=None, w_batch=None)
                heapq.heappush(self._events, (self.t + delay, self._eseq,
                                              _EV_UPLOAD, ghost))
                self._eseq += 1
            return False
        self._ev_up[c.client] += 1   # the failed attempt sent real bytes
        if fate == "transient" and c.attempt <= fm.cfg.max_retries:
            fm.total_retries += 1
            if tel:
                self.telemetry.event(
                    "retry", ts=self.t, round_idx=self.round_idx,
                    client=int(c.client), attempt=c.attempt + 1)
            delay = fm.backoff(c.attempt)
            c.attempt += 1
            # still in flight (the slot stays held): same contribution,
            # redelivered after exponential backoff
            heapq.heappush(self._events,
                           (self.t + delay, self._eseq, _EV_UPLOAD, c))
            self._eseq += 1
            return True
        # lost for good: mid-flight drop, retry budget exhausted, or
        # rejected by the corruption screen
        reason = {"drop": "drop", "transient": "exhausted",
                  "corrupt": "corrupt"}[fate]
        self._n_inflight -= 1
        self._ev_dropped += 1
        self._exec.release(self, c)
        fm.total_drops += 1
        if fate == "corrupt":
            fm.total_corrupt += 1
            until = fm.record_offense(int(c.client), self.round_idx)
            if until is not None and tel:
                self.telemetry.event(
                    "quarantine", ts=self.t, round_idx=self.round_idx,
                    client=int(c.client), until_round=until)
        if tel:
            self.telemetry.event(
                "upload_drop", ts=self.t, round_idx=self.round_idx,
                client=int(c.client), reason=reason,
                in_flight=self._n_inflight, stalled=len(self._stalled))
        return True

    def _step_async(self) -> SimMetrics:
        """One aggregation event: pump the per-client event queue until the
        buffer holds ``buffer_size`` contributions, staleness-merge them in
        arrival order, and advance the server version.

        Event lifecycle: select (key-stream cohort draw) -> start (slot
        permitting; same-instant starts batch into one round-function call)
        -> upload (arrival frees a slot, which immediately un-stalls the
        oldest waiting dispatch). Fresh cohorts are drawn at step entry
        whenever the system holds less than one cohort of work, and
        mid-fill whenever the queue runs dry -- both on the sync key
        stream, which is what keeps max_concurrency >= cohort +
        buffer == cohort bit-identical to sync.run(N).
        """
        t_start = self.t
        self._ev_down = np.zeros(self.cfg.m, np.int64)
        self._ev_up = np.zeros(self.cfg.m, np.int64)
        self._ev_contacted = 0
        self._ev_dropped = 0
        if self.telemetry.enabled:
            self.telemetry.event("round_start", ts=self.t,
                                 round_idx=self.round_idx, policy="async",
                                 version=self._version)
        if self._in_system() < self._cohort:
            self._select_cohort()
        buffer: list[_Contribution] = []
        dry = 0
        n_selects = 0
        while len(buffer) < self._buffer_k and dry < _MAX_DRY_DISPATCHES:
            # un-stall slot-blocked dispatches first: they have been waiting
            # since an earlier instant and outrank anything queued later
            if self._stalled and self._free_slots() >= 1:
                group = [self._stalled.popleft()]
                while self._stalled and len(group) < self._free_slots():
                    group.append(self._stalled.popleft())
                self._fire_group(group)
                continue
            if not self._events:
                if self._faults is not None \
                        and n_selects >= _MAX_FAULT_SELECTS:
                    # graceful degradation under heavy loss: stop waiting
                    # for a full buffer and merge whatever survived (an
                    # empty buffer abandons the event, like a missed
                    # deadline)
                    break
                n_selects += 1
                # nothing in flight and nothing startable: draw fresh work
                dry = dry + 1 if self._select_cohort() == 0 else 0
                continue
            t_ev, _, kind, payload = heapq.heappop(self._events)
            self.t = max(self.t, t_ev)
            if kind == _EV_START:
                self._n_queued_starts -= 1
                if self._free_slots() < 1:
                    self._stalled.append(payload)
                    continue
                group = [payload]
                # same-instant starts batch into ONE dispatch (one round
                # function call, one key advance) while slots allow --
                # an uncapped server therefore dispatches whole cohorts
                while (self._events and len(group) < self._free_slots()
                       and self._events[0][0] == t_ev
                       and self._events[0][2] == _EV_START):
                    group.append(heapq.heappop(self._events)[3])
                    self._n_queued_starts -= 1
                self._fire_group(group)
                continue
            c = payload
            if self._faults is not None and self._handle_faulty_upload(c):
                continue
            self._n_inflight -= 1
            self._ev_up[c.client] += 1
            buffer.append(c)
            if self.telemetry.enabled:
                self.telemetry.event(
                    "upload_arrival", ts=self.t, round_idx=self.round_idx,
                    client=int(c.client), version=c.version,
                    in_flight=self._n_inflight,
                    stalled=len(self._stalled))

        staleness = [self._version - c.version for c in buffer]
        for c, s in zip(buffer, staleness):
            gamma = participation.staleness_weight(s, self.sim.staleness_exp)
            if self._faults is not None:
                # dedup sequence number of the merged delivery: any later
                # redelivery of the same attempt is discarded at arrival
                self._faults.seen.add((c.client, c.serial, c.attempt))
            self._exec.merge(self, c, s, gamma)
            if self.telemetry.enabled:
                if self.sim.codec is not None:
                    self.telemetry.event(
                        "codec_encode", ts=self.t, round_idx=self.round_idx,
                        client=int(c.client),
                        **codec_event_attrs(self.sim.codec, n_clients=1,
                                            up_bytes=self._up_bytes))
                self.telemetry.event(
                    "merge", ts=self.t, round_idx=self.round_idx,
                    client=int(c.client), staleness=int(s),
                    gamma=float(gamma))
            if self._privacy is not None and self.sim.privacy.eps > 0:
                # charged at MERGE time -- when the noisy payload is
                # consumed; staleness keeps the charge attributable to
                # its dispatch round in the event stream
                tot = self._privacy.charge(int(c.client))
                if self.telemetry.enabled:
                    self.telemetry.event(
                        "privacy_charge", ts=self.t,
                        round_idx=self.round_idx, client=int(c.client),
                        eps=self.sim.privacy.eps, eps_total=tot,
                        staleness=int(s))
        if buffer:
            self._version += 1
        elif self.telemetry.enabled:
            self.telemetry.event("abandon", ts=self.t,
                                 round_idx=self.round_idx,
                                 n_contacted=self._ev_contacted)

        if self._privacy is not None:
            # every billed upload attempt carried one mask-pair exchange
            # (its bytes are folded into _up_bytes, so the ledger record
            # below charges them; this keeps the model's counters in
            # lockstep with it)
            attempts = int(self._ev_up.sum())
            mbytes = self._privacy.bill_masks(attempts)
            if self.sim.privacy.secure_agg and attempts \
                    and self.telemetry.enabled:
                self.telemetry.event(
                    "mask_exchange", ts=self.t, round_idx=self.round_idx,
                    attempts=attempts, bytes=mbytes)
        brec = self.ledger.record_counts(
            down_counts=self._ev_down, up_counts=self._ev_up,
            down_bytes=self._down_bytes, up_bytes=self._up_bytes,
            ts=self.t, round_idx=self.round_idx)
        m = make_sim_metrics(
            round_idx=self.round_idx, t_round=self.t - t_start,
            t_total=self.t, n_contacted=self._ev_contacted,
            n_aggregated=len(buffer), n_dropped=self._ev_dropped,
            brec=brec, abandoned=not buffer, staleness=staleness)
        self.metrics.append(m)
        self.round_idx += 1
        return m

    def run(self, rounds: int) -> list[SimMetrics]:
        return [self.step() for _ in range(rounds)]

    # -- exact rewind (scan-engine termination replay) ----------------------

    def snapshot(self) -> dict:
        """Deep copy of EVERYTHING a later :meth:`restore` needs to replay
        the simulation bit-for-bit from this point: algorithm state and
        codec memory (fresh device buffers, so chunk donation cannot
        invalidate them), the host RNG stream, the clock/round counters,
        the byte ledger, the telemetry stream position, and -- under the
        async policy -- the whole event-loop state (heap, stalled FIFO,
        payload table). The snapshot stays valid across multiple restores.
        """
        snap = {
            "state": copy_tree(self.state),
            "H": None if self._H is None else copy_tree(self._H),
            "rng": copy.deepcopy(self._rng.bit_generator.state),
            "t": self.t,
            "round_idx": self.round_idx,
            "n_metrics": len(self.metrics),
            "last_rm": self.last_round_metrics,
            "host_syncs": self.host_syncs,
            "ledger": self.ledger.checkpoint(),
            "tel_mark": self.telemetry.mark(),
        }
        if self.sim.policy == "adaptive":
            snap["ewma"] = self.deadlines.ewma.copy()
        if self._faults is not None:
            snap["faults"] = self._faults.state_snapshot()
        if self._privacy is not None:
            snap["privacy"] = self._privacy.state_snapshot()
        if self.sim.policy == "async":
            snap["async"] = {
                "version": self._version,
                "serial": self._serial,
                "eseq": self._eseq,
                # upload payloads are MUTABLE (the executor rewrites their
                # batch refs), so each gets its own shallow copy; start
                # payloads are immutable (client, duration) tuples
                "events": [
                    (t, seq, kind,
                     dataclasses.replace(p) if kind == _EV_UPLOAD else p)
                    for (t, seq, kind, p) in self._events],
                "stalled": collections.deque(self._stalled),
                "n_inflight": self._n_inflight,
                "n_queued_starts": self._n_queued_starts,
                "cohort_live": self._cohort_live.copy(),
                "table": None if self._async_table is None
                else self._async_table.clone(),
            }
        return snap

    def restore(self, snap: dict) -> None:
        """Rewind to a :meth:`snapshot`; the snapshot remains reusable
        (everything mutable is copied again on the way out)."""
        self.state = copy_tree(snap["state"])
        self._H = None if snap["H"] is None else copy_tree(snap["H"])
        self._rng.bit_generator.state = copy.deepcopy(snap["rng"])
        self.t = snap["t"]
        self.round_idx = snap["round_idx"]
        del self.metrics[snap["n_metrics"]:]
        self.last_round_metrics = snap["last_rm"]
        self.host_syncs = snap["host_syncs"]
        self.ledger.restore(snap["ledger"])
        self.telemetry.rewind(snap["tel_mark"])
        if self.sim.policy == "adaptive":
            self.deadlines.ewma = snap["ewma"].copy()
        if self._faults is not None:
            self._faults.state_restore(snap["faults"])
        if self._privacy is not None:
            self._privacy.state_restore(snap["privacy"])
        if self.sim.policy == "async":
            a = snap["async"]
            self._version = a["version"]
            self._serial = a["serial"]
            self._eseq = a["eseq"]
            self._events = [
                (t, seq, kind,
                 dataclasses.replace(p) if kind == _EV_UPLOAD else p)
                for (t, seq, kind, p) in a["events"]]
            self._stalled = collections.deque(a["stalled"])
            self._n_inflight = a["n_inflight"]
            self._n_queued_starts = a["n_queued_starts"]
            self._cohort_live = a["cohort_live"].copy()
            table = a["table"]
            self._async_table = None if table is None else table.clone()
            if self._async_table is not None:
                # table-backed contributions must reference THIS restore's
                # table clone (the snapshot-time arrays may have been
                # donated into a later chunk before the rewind)
                for _, _, kind, p in self._events:
                    if kind == _EV_UPLOAD and p.slot >= 0:
                        p.z_batch = self._async_table.z
                        p.w_batch = self._async_table.w
                        p.row = p.slot
