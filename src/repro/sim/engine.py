"""Fused on-device round engine: scan-compiled multi-round execution.

The eager simulation driver (``FedSim.step``) pays one full host round-trip
per federated round: a jit dispatch for the selection mask, a device->host
transfer of the candidates, a host->device upload of the participation
mask, a jit dispatch for the round function, and (in the CLI) a blocking
``float(objective)``. At paper scale the round math itself is microseconds
of FLOPs, so wall-clock is dominated by dispatch overhead -- not by
anything the paper analyzes.

``run_rounds`` removes the per-round host synchronization for the clocked
policies (sync / deadline / adaptive / overselect) while reproducing the
eager trajectory BIT-FOR-BIT (state leaves, PRNG key, simulated clock,
byte-ledger totals -- pinned by tests/test_engine.py):

1. **Arrival precompute (host).** Per-round arrival times come from the
   host RNG exactly as in the eager path -- one ``round_arrivals`` draw per
   round, same call order, so the stream is unchanged. For a K-round chunk
   this is one (K, m) float64 array, computed up front.

2. **Candidate-stream scan (device).** The selection key stream is
   deterministic given which rounds abandon (an abandoned round does not
   advance the key), so one jitted ``lax.scan`` over the chunk replays the
   per-round ``split``/sampler calls and returns every round's candidate
   mask in a single transfer. Because abandonment itself depends on the
   masks, the engine iterates candidate-stream -> host policy to a
   fixpoint; each pass can only extend the correct abandoned-prefix, so it
   converges in 1 + (#rounds whose abandoned flag changed) passes --
   one pass in the common no-abandon case.

3. **Policy replay (host, float64).** Mask + round-duration logic is
   replayed in numpy, mirroring ``FedSim._apply_policy`` operation for
   operation (including the float32 casts the jit'd ``arrival_mask``
   helpers apply), so masks, durations, the simulated clock, and the byte
   ledger are bit-identical to eager. This is O(K m) numpy -- negligible.

4. **Round scan (device, donated buffers).** The (K, m) mask stream is
   uploaded once and ``jax.lax.scan`` runs K rounds in one XLA program
   (``core.fedepm.scan_round`` / ``core.baselines.scan_round`` bodies;
   with a codec the merge is fused into an extended body). The carried
   state and EF codec memory are donated (``donate_argnums``), so XLA
   reuses their buffers across chunks instead of copying. Per-round
   metrics stack on-device and transfer in ONE ``jax.device_get`` per
   chunk. Abandoned rounds carry state through via a ``tree_where`` on the
   whole carry -- the round body still runs, its result is discarded
   exactly.

Donation invariant: ``run_rounds`` snapshots the entry state (one copy)
before the first donating call, so references the caller still holds --
e.g. the ``state=s0`` it passed to ``FedSim`` -- stay valid; every
intermediate chunk state is engine-owned and safely donated.

Async record/replay (policy="async")
------------------------------------
The async policy is event-driven (client-level queue, data-dependent
control flow), so it cannot be masked into the clocked round scan above.
Instead the engine RECORDS it: ``FedSim._step_async`` -- the one
scheduling pump both engines share -- runs C aggregation events with a
recording executor plugged into its device-work seam. Candidate draws
replay from a precomputed fire-count key stream (``_CandStream``: the
selection key/counter advance only when a dispatch fires, so the mask
stream is a pure function of the chunk-entry state); fires and merges
append host metadata (masks, table slots, staleness weights, codec
serials) to an op program instead of dispatching jit calls. One compiled
``lax.scan`` then replays the program (``_build_async_chunk_fn``), one
step per dispatch: the step runs the unmodified round function and
writes the dispatch group's fresh Z/W rows into a fixed-capacity
on-device payload TABLE (``_AsyncTable`` -- the bounded in-flight set;
slots alloc lowest-first at dispatch, free at merge), then an inner scan
folds the merges recorded before the next dispatch through the shared
``server.merge_contribution`` against the table rows. Both levels are
branch-free -- everything is validity-masked ``tree_where`` selection,
never ``lax.cond``/``lax.switch``, because conditional lowering perturbs
the round's fused reductions by ~1 ulp. State, EF memory, table and the
optional w_tau stack are all donated. Every host-side
quantity (clock, heap order, staleness, metrics, ledger, telemetry) is
computed by the SAME pump code as eager, and every device value is the
same math on the same bits, so the trajectory -- including the telemetry
event stream -- is bit-for-bit the eager one
(tests/test_engine_async.py).

Fault injection (``SimConfig.faults``, repro.sim.faults) is entirely
host-side: the clocked policy replay resolves the fault chains inside
``_policy_stream_host`` (snapshot/restoring the model around fixpoint
passes, like the adaptive EWMA), and the async recording pass runs the
same pump defenses as eager -- no compiled program changes at all, so
fault-injected trajectories and telemetry streams stay bit-for-bit
across engines (tests/test_faults.py).

Upload privacy (``SimConfig.privacy``, repro.privacy) splits the same
way: the clip transform is device work, so a noisy config swaps the
chunk bodies' codec round-trips for the private ones
(``transport.private_roundtrip`` / ``private_ef_roundtrip``), while the
noise DRAWS are host work fed in as data -- ``run_rounds`` stacks one
``transport.draw_unit_noise`` tree per round (privacy stream folded on
the round index) into the clocked scan's xs, and the async replay
stacks one per recorded merge (folded on the upload serial), the exact
draws the eager merge programs consume, so noisy trajectories stay
bit-for-bit across engines (tests/test_privacy.py; see
``draw_unit_noise`` for why in-body transcendentals would break this).
The accountant and secure-agg mask billing are host bookkeeping,
emitted by the SAME ``server.apply_clocked_privacy`` helper the eager
step calls (async charges live inside the shared pump, which the
recording pass runs).

Client-axis sharding: ``run_rounds(..., mesh=...)`` lays the stacked
(m, ...) state leaves out over a device mesh's "data" axis (the repo's
logical rule client -> data, sharding/rules.py + specs.leaf_spec rails)
before the compiled chunks run, so XLA partitions the per-client round
math data-parallel; a single-device mesh is bit-identical to unsharded.
Architecture notes and how to read ``BENCH_engine.json``: docs/perf.md.
"""
from __future__ import annotations

import heapq
import math
from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.profiler import TraceAnnotation

from repro.core import baselines, fedepm, participation
from repro.core.treeutil import tmap, tree_where, tree_where_client
from repro.sim import clients as simclients
from repro.sim.server import (_EAGER_ASYNC_EXEC, _EV_UPLOAD, FedSim,
                              SimMetrics, apply_clocked_privacy, copy_tree,
                              emit_clocked_round_events, fifo_cache_get,
                              make_sim_metrics, merge_contribution)
from repro.sim.transport import (codec_roundtrip, draw_unit_noise,
                                 ef_roundtrip, private_ef_roundtrip,
                                 private_roundtrip)

_SCAN_POLICIES = ("sync", "deadline", "adaptive", "overselect")


class EngineResult(NamedTuple):
    metrics: list            # SimMetrics, one per round (same as eager)
    w_tau: np.ndarray | None  # (K, ...) per-round broadcast point, host side


# ---------------------------------------------------------------------------
# host-side policy replay (bit-identical to FedSim._apply_policy)
# ---------------------------------------------------------------------------

def _arrival_mask_host(cand: np.ndarray, arr: np.ndarray,
                       deadline) -> np.ndarray:
    """numpy replica of participation.arrival_mask as the eager path calls
    it: arrivals (and per-client cutoffs) pass through jnp.asarray, i.e.
    FLOAT32, before the comparison -- replicate the cast exactly."""
    arr32 = arr.astype(np.float32)
    dl32 = np.asarray(deadline, dtype=np.float32)
    with np.errstate(invalid="ignore"):
        return cand & np.isfinite(arr32) & (arr32 <= dl32)


def _first_arrivals_host(cand: np.ndarray, arr: np.ndarray,
                         n_keep: int) -> np.ndarray:
    """numpy replica of participation.first_arrivals_mask (float32 sort
    keys, stable order -- jnp.argsort's default)."""
    t = np.where(cand, arr.astype(np.float32), np.float32(np.inf))
    order = np.argsort(t, kind="stable")
    rank = np.empty(len(t), np.int64)
    rank[order] = np.arange(len(t))
    return (rank < n_keep) & np.isfinite(t)


def _policy_round_host(sim: FedSim, candidates: np.ndarray,
                       arrivals: np.ndarray):
    """One round of FedSim._apply_policy, replayed host-side.

    Mask semantics use the same float32 comparisons as the jit'd helpers;
    round durations use the same float64 numpy arithmetic as the eager
    driver. Returns (mask, duration); for the adaptive policy this also
    folds the round's observations into sim.deadlines (the caller
    snapshots/restores the EWMA around fixpoint passes).
    """
    pol = sim.sim.policy
    t_cand = np.where(candidates, arrivals, np.inf)
    if pol == "sync":
        mask = _arrival_mask_host(candidates, arrivals, np.inf)
        dur = float(t_cand[mask].max()) if mask.any() else 0.0
        return mask, dur
    if pol == "deadline":
        dl = sim.sim.deadline
        mask = _arrival_mask_host(candidates, arrivals, dl)
        if not candidates.any():
            return mask, 0.0
        finite = t_cand[np.isfinite(t_cand)]
        if np.isfinite(t_cand[candidates]).all() \
                and (t_cand[candidates] <= dl).all():
            return mask, float(t_cand[candidates].max())
        if np.isfinite(dl):
            return mask, float(dl)
        return mask, float(finite.max()) if finite.size else 0.0
    if pol == "adaptive":
        cut = sim.deadlines.cutoffs()
        mask = _arrival_mask_host(candidates, arrivals, cut)
        wait = np.where(candidates, np.minimum(arrivals, cut), np.inf)
        finite = wait[np.isfinite(wait)]
        dur = float(finite.max()) if finite.size else 0.0
        sim.deadlines.observe(candidates, arrivals)
        return mask, dur
    if pol == "overselect":
        mask = _first_arrivals_host(candidates, arrivals, sim._n_keep)
        dur = float(t_cand[mask].max()) if mask.any() else 0.0
        return mask, dur
    raise ValueError(f"unknown policy {pol!r}")


def _policy_stream_host(sim: FedSim, candidates: np.ndarray,
                        arrivals: np.ndarray):
    """Replay C rounds of policy logic.

    Returns (masks, durs, abandoned, rec_ups, cands_eff, arrs_eff, fouts):
    the EFFECTIVE candidate/arrival streams the policy saw (fault
    resolution applied per round, exactly as the eager ``step()`` does
    before ``_apply_policy``) plus the per-round fault outcomes (None
    entries without a fault model). Mutates the fault model's state in
    round order -- fixpoint callers snapshot/restore it around passes,
    like the adaptive EWMA.
    """
    C, m = candidates.shape
    masks = np.zeros((C, m), bool)
    rec_ups = np.zeros((C, m), bool)
    durs = np.zeros(C, np.float64)
    abandoned = np.zeros(C, bool)
    fm = sim._faults
    cands_eff = np.asarray(candidates, bool).copy()
    arrs_eff = np.asarray(arrivals, np.float64).copy()
    fouts: list = [None] * C
    for t in range(C):
        cand, arr = cands_eff[t], arrs_eff[t]
        if fm is not None:
            fo = fm.apply_clocked(
                round_idx=sim.round_idx + t, candidates=cand, arrivals=arr,
                cutoff=sim.sim.deadline
                if sim.sim.policy == "deadline" else math.inf)
            cand, arr = fo.candidates, fo.arrivals
            cands_eff[t], arrs_eff[t] = cand, arr
            fouts[t] = fo
        mask, dur = _policy_round_host(sim, cand, arr)
        ab = bool(cand.any() and not mask.any())
        if ab:
            rec = np.zeros(m, bool)
        elif sim.sim.policy == "adaptive":
            rec = mask
        else:
            rec = cand & np.isfinite(arr) & (arr <= dur + 1e-12)
        masks[t], durs[t], abandoned[t], rec_ups[t] = mask, dur, ab, rec
    return masks, durs, abandoned, rec_ups, cands_eff, arrs_eff, fouts


# ---------------------------------------------------------------------------
# device-side streams (compiled once per FedSim, cached on the instance)
# ---------------------------------------------------------------------------

# compiled-function caches, shared ACROSS FedSim instances: two sims with
# the same (round fn, loss fn, algorithm config, codec, batches) -- e.g.
# the eager and scan arms of a benchmark, or consecutive CLI runs in one
# process -- reuse one traced/compiled program instead of re-tracing per
# instance. Batches are keyed by IDENTITY and stay closure-captured like
# the eager driver's jit does: embedding them as XLA constants is what
# keeps the scan bit-identical to eager (constant-vs-argument batches
# change XLA's folding by 1 ulp); the cached closure keeps them alive, so
# the id cannot be recycled while the entry exists. Both caches are
# bounded (server.fifo_cache_get): a chunk-fn closure pins its whole
# dataset on device, so an unbounded cache would leak one dataset per
# swept task.
_CAND_STREAM_CACHE: dict = {}
_CHUNK_FN_CACHE: dict = {}


def _candidate_stream_fn(sim: FedSim):
    key = (sim.cfg, sim.sim.policy, sim.sim.overselect_factor)
    return fifo_cache_get(_CAND_STREAM_CACHE, key,
                          lambda: _build_candidate_stream(sim), cap=32)


def _chunk_fn(sim: FedSim, collect_w_tau: bool):
    key = (sim._round_fn, sim._loss_fn, sim.cfg, sim.sim.codec, sim._ef,
           sim._privacy_tx, collect_w_tau, id(sim._batches))
    return fifo_cache_get(_CHUNK_FN_CACHE, key,
                          lambda: _build_chunk_fn(sim, collect_w_tau),
                          cap=32)


def _make_selector(sim: FedSim):
    """Jit-safe candidate selector ``(k_sel, k) -> (m,) bool`` for ``sim``.

    Replicates exactly what the algorithm's default mask function computes
    from the round's 3-way key split -- ONE definition shared by the
    clocked candidate-stream scan and the async fire-count stream, so
    neither replay can drift from the eager ``sim._candidates`` draw.
    """
    cfg = sim.cfg
    m, k0 = cfg.m, cfg.k0
    if sim.sim.policy == "overselect":
        rho_eff = min(1.0, cfg.rho * sim.sim.overselect_factor)

        def select(k_sel, k):
            return participation.sample_uniform(k_sel, m, rho_eff)
        return select
    sampler = getattr(cfg, "sampler", "uniform")
    if sampler == "uniform":
        def select(k_sel, k):
            return participation.sample_uniform(k_sel, m, cfg.rho)
    elif sampler == "coverage":
        def select(k_sel, k):
            return participation.sample_coverage(
                k_sel, m, cfg.rho, k // k0, cfg.s0)
    elif sampler == "full":
        def select(k_sel, k):
            return jnp.ones((m,), bool)
    else:
        raise ValueError(f"unknown sampler {sampler!r}")
    return select


def _build_candidate_stream(sim: FedSim):
    """Jitted scan replaying the per-round selection key splits.

    carry = (key, k): the key advances (first output of the round's
    3-way split) and k advances by k0 only on non-abandoned rounds,
    mirroring how the eager driver leaves the state untouched when a round
    is abandoned. Returns the (C, m) candidate-mask stream.
    """
    k0 = sim.cfg.k0
    select = _make_selector(sim)

    def stream(key, k, abandoned):
        def body(carry, ab):
            key, k = carry
            next_key, k_sel, _ = jax.random.split(key, 3)
            cand = select(k_sel, k)
            key = jnp.where(ab, key, next_key)
            k = jnp.where(ab, k, k + jnp.asarray(k0, k.dtype))
            return (key, k), cand

        _, cands = jax.lax.scan(body, (key, k), abandoned)
        return cands

    return jax.jit(stream)


def _build_chunk_fn(sim: FedSim, collect_w_tau: bool):
    """Jitted K-round scan with donated (state, codec-memory) buffers.

    The body is the scan-compatible round (core.fedepm.scan_round /
    the equivalent baselines body) with the upload-codec merge fused in;
    ys stacks per-round RoundMetrics (and optionally w_tau) on-device.
    """
    round_fn = sim._round_fn
    batches, loss_fn, cfg = sim._batches, sim._loss_fn, sim.cfg
    codec, ef = sim.sim.codec, sim._ef
    privacy = sim._privacy_tx
    if sim.alg == "fedepm":
        def core_body(st, xs):
            return fedepm.scan_round(st, xs, batches, loss_fn, cfg)
    else:
        def core_body(st, xs):
            return baselines.scan_round(st, xs, batches, loss_fn, cfg,
                                        round_fn)

    def chunk(state, H, codec_key, masks, abandoned, round_idx, noise):
        def body(carry, x):
            st, Hc = carry
            mask, ab, ridx, ns = x
            if codec is None and privacy is None:
                st2, rm = core_body(st, (mask, ab))
                ys = (rm, st2.w_tau) if collect_w_tau else (rm,)
                return (st2, Hc), ys
            new_st, rm = round_fn(st, batches, loss_fn, cfg, mask=mask)
            ckey = jax.random.fold_in(codec_key, ridx)
            if privacy is not None:
                # noisy merge: same private round-trips as the eager
                # server's merge programs; the round's unit-noise tree
                # arrives as scan xs (host-drawn by run_rounds from the
                # dedicated privacy stream -- data, so both engines
                # perturb bit-identically)
                if ef:
                    dec = private_ef_roundtrip(new_st.Z, Hc, ckey, ns,
                                               codec, privacy)
                    new_st = new_st._replace(
                        Z=tree_where_client(mask, dec, st.Z))
                    Hn = tree_where_client(mask, dec, Hc)
                else:
                    dec = private_roundtrip(new_st.Z, st.Z, ckey, ns,
                                            codec, privacy)
                    new_st = new_st._replace(
                        Z=tree_where_client(mask, dec, st.Z))
                    Hn = Hc
            elif ef:
                dec = ef_roundtrip(new_st.Z, Hc, ckey, codec)
                new_st = new_st._replace(
                    Z=tree_where_client(mask, dec, st.Z))
                Hn = tree_where_client(mask, dec, Hc)
            else:
                dec = codec_roundtrip(new_st.Z, st.Z, ckey, codec)
                new_st = new_st._replace(
                    Z=tree_where_client(mask, dec, st.Z))
                Hn = Hc
            st2 = tree_where(ab, st, new_st)
            Hc2 = tree_where(ab, Hc, Hn)
            ys = (rm, st2.w_tau) if collect_w_tau else (rm,)
            return (st2, Hc2), ys

        return jax.lax.scan(body, (state, H),
                            (masks, abandoned, round_idx, noise))

    return jax.jit(chunk, donate_argnums=(0, 1))


def _copy_tree(tree):
    return tmap(lambda x: jnp.array(x, copy=True), tree)


# ---------------------------------------------------------------------------
# async record/replay (policy="async")
# ---------------------------------------------------------------------------

#: async candidate masks are computed in blocks of this many fires per
#: device dispatch (one host transfer per block, not per draw)
_ASYNC_STREAM_BLOCK = 64

_ASYNC_STREAM_CACHE: dict = {}


def _async_stream_fn(sim: FedSim):
    def build():
        select = _make_selector(sim)
        k0 = sim.cfg.k0

        def block(key, k):
            def body(carry, _):
                key, k = carry
                next_key, k_sel, _ = jax.random.split(key, 3)
                cand = select(k_sel, k)
                return (next_key, k + jnp.asarray(k0, k.dtype)), cand

            (key, k), cands = jax.lax.scan(
                body, (key, k), None, length=_ASYNC_STREAM_BLOCK)
            return cands, key, k

        return jax.jit(block)

    return fifo_cache_get(_ASYNC_STREAM_CACHE, (sim.cfg, sim.sim.policy),
                          build, cap=32)


class _CandStream:
    """Async candidate masks indexed by FIRE COUNT (host-side cache).

    The selection key and step counter advance ONLY when a dispatch group
    fires (one key split + k0 per round-function call), never on the draw
    itself -- so the whole mask stream of a recording chunk is a pure
    function of the chunk-entry algorithm state: mask ``n`` is what the
    eager server would draw after ``n`` fires. An all-offline cohort's
    retry re-draws the SAME index (no fire happened), reproducing eager's
    repeated draw from the unchanged key with fresh availability.
    """

    def __init__(self, sim: FedSim):
        self._sim = sim
        self._fn = _async_stream_fn(sim)
        self._key = sim.state.key
        self._k = sim.state.k
        self._masks: list[np.ndarray] = []

    def mask(self, n_fires: int) -> np.ndarray:
        while n_fires >= len(self._masks):
            with TraceAnnotation("repro.engine.candidates",
                                 round=self._sim.round_idx):
                cands, self._key, self._k = self._fn(self._key, self._k)
                self._masks.extend(np.asarray(cands))
            self._sim.host_syncs += 1
        return self._masks[n_fires]


class _AsyncTable:
    """Fixed-capacity on-device payload table: the bounded in-flight set.

    One row per outstanding upload: ``z``/``w`` are (cap, ...) pytrees
    whose row ``slot`` holds a dispatched client's upload/iterate rows,
    written by the fire op that dispatched it and read back by the merge
    op that folds it in. A table IS a ``_Contribution`` batch (``slot`` ==
    batch row), so the eager merge path consumes table-backed
    contributions through the same ``merge_contribution`` call. Slots
    allocate lowest-index-first from a min-heap -- a deterministic rule,
    so recorded slot assignments are reproducible -- and free when their
    contribution merges. With the ``event_table_capacity`` knob pinned the
    table never grows (overflow raises, naming the knob); unset, it
    doubles on demand (each capacity compiles one more chunk program).
    """

    def __init__(self, Z, W, cap: int, *, fixed: bool):
        self.cap = cap
        self.fixed = fixed
        self.z = tmap(lambda x: jnp.zeros((cap,) + x.shape[1:], x.dtype), Z)
        self.w = tmap(lambda x: jnp.zeros((cap,) + x.shape[1:], x.dtype), W)
        self._free = list(range(cap))

    def alloc(self) -> int:
        if not self._free:
            if self.fixed:
                raise ValueError(
                    f"async event table overflow: all {self.cap} slots "
                    f"hold in-flight uploads; raise the engine's "
                    f"event_table_capacity knob (or unset it to let the "
                    f"table grow on demand)")
            grow = self.cap
            self.z = tmap(lambda x: jnp.concatenate(
                [x, jnp.zeros((grow,) + x.shape[1:], x.dtype)]), self.z)
            self.w = tmap(lambda x: jnp.concatenate(
                [x, jnp.zeros((grow,) + x.shape[1:], x.dtype)]), self.w)
            self._free = list(range(self.cap, self.cap + grow))
            self.cap += grow
        return heapq.heappop(self._free)

    def free(self, slot: int) -> None:
        heapq.heappush(self._free, slot)

    def clone(self) -> "_AsyncTable":
        t = object.__new__(_AsyncTable)
        t.cap, t.fixed = self.cap, self.fixed
        t.z, t.w = copy_tree(self.z), copy_tree(self.w)
        t._free = list(self._free)
        return t


class _RecordAsyncExec:
    """Recording executor: defers device work into a replayable op program.

    Plugged into ``FedSim._step_async``'s executor seam for the chunk's C
    steps. Candidate draws replay from the fire-count stream; ``fire``/
    ``merge`` append host metadata only (masks, table slots, staleness
    weights, codec serials) -- no jit dispatch happens until the recorded
    program replays as ONE compiled scan. Slot lifecycle resolves at
    record time (alloc at fire, free at merge); replay executes ops in
    recorded order, so a slot reused by a later fire is always rewritten
    AFTER the merge that read it.
    """

    recording = True

    def __init__(self, stream: _CandStream, table: _AsyncTable):
        self.stream = stream
        self.table = table
        self.ops: list[dict] = []
        self.n_fires = 0
        self.cur_step = 0

    def draw_candidates(self, sim) -> np.ndarray:
        return self.stream.mask(self.n_fires)

    def fire(self, sim, group, mask: np.ndarray, contribs) -> None:
        slots = []
        for c in contribs:
            c.slot = self.table.alloc()
            slots.append((c.slot, c.client))
        self.ops.append({
            "kind": 0, "step": self.cur_step, "mask": mask,
            "agg": (sim._cohort_live | mask)
            if sim._step_agg is not None else mask,
            "slots": slots})
        self.n_fires += 1

    def merge(self, sim, c, staleness: int, gamma: float) -> None:
        self.ops.append({
            "kind": 1, "step": self.cur_step, "slot": c.slot,
            "client": c.client, "serial": c.serial,
            "gamma": np.float32(gamma)})
        self.table.free(c.slot)

    def release(self, sim, c) -> None:
        # fault injection: the upload was lost/rejected -- its table slot
        # frees WITHOUT a merge op, so the replay never reads the row (the
        # non-merge is exact: no op recorded, no device work)
        self.table.free(c.slot)
        c.slot = -1


def _async_chunk_fn(sim: FedSim, collect_w_tau: bool):
    key = ("async", sim._round_fn, sim._loss_fn, sim.cfg, sim.sim.codec,
           sim._ef, sim._privacy_tx, collect_w_tau, id(sim._batches))
    return fifo_cache_get(
        _CHUNK_FN_CACHE, key,
        lambda: _build_async_chunk_fn(sim, collect_w_tau), cap=32)


def _build_async_chunk_fn(sim: FedSim, collect_w_tau: bool):
    """Compiled async replay: ONE ``lax.scan`` over the recorded program.

    The program is GROUPED: one scan step = one dispatch (validity-masked)
    followed by the merges recorded between it and the next dispatch (an
    inner ``lax.scan`` over ``Mmax`` validity-masked merge records). The
    carry is (algorithm state, EF memory, table z, table w, w_tau stack),
    every buffer donated.

    There are NO data-dependent conditionals anywhere in the body -- no
    ``lax.switch``, no ``lax.cond``. Wrapping the round function in either
    changes how XLA fuses its reductions and moves the DP-noise arithmetic
    by ~1 ulp relative to the eager jit; a plain scan body that runs the
    round unconditionally and selects outcomes with ``tree_where`` is
    bit-identical (the same pattern the clocked chunk uses for abandoned
    rounds, and the differential tests pin it). Invalid (padding /
    merge-only) steps therefore still RUN the round on a zero mask and
    discard every output; invalid merge records merge slot 0 and discard.

    A valid step's fire is exactly the eager fire: broadcast/key/counter
    advance, the dispatch group's fresh Z/W rows written into their
    recorded table slots (exact row copies, bit-equal to the eager
    per-group gather). Merges call the shared ``merge_contribution`` with
    the post-fire table as the batch and the recorded slot as the batch
    row. Step counts pad to small buckets so chunk programs compile per
    bucket, not per step count.
    """
    round_fn = sim._round_fn
    batches, loss_fn, cfg = sim._batches, sim._loss_fn, sim.cfg
    codec, ef = sim.sim.codec, sim._ef
    privacy = sim._privacy_tx
    use_agg = sim.alg != "fedepm"

    def chunk(state, H, tz, tw, ws, codec_key, xs):
        def body(carry, x):
            st, Hc, tz, tw, ws = carry
            if use_agg:
                new_st, rm = round_fn(st, batches, loss_fn, cfg,
                                      mask=x["mask"], agg_mask=x["agg"])
            else:
                new_st, rm = round_fn(st, batches, loss_fn, cfg,
                                      mask=x["mask"])
            v = x["fire_valid"]
            st2 = st._replace(
                w_tau=tree_where(v, new_st.w_tau, st.w_tau),
                k=jnp.where(v, new_st.k, st.k),
                key=jnp.where(v, new_st.key, st.key))
            # invalid steps carry slot_src == -1 everywhere: no writes
            src = jnp.clip(x["slot_src"], 0)
            upd = x["slot_src"] >= 0
            tz2 = tree_where_client(
                upd, tmap(lambda a: a[src], new_st.Z), tz)
            tw2 = tree_where_client(
                upd, tmap(lambda a: a[src], new_st.W), tw)
            if collect_w_tau:
                ws2 = tmap(
                    lambda s, w: jax.lax.dynamic_update_index_in_dim(
                        s, w, x["step"], 0), ws, st2.w_tau)
                ws = tree_where(v, ws2, ws)

            def mbody(mc, mx):
                stc, Hcc = mc
                ckey = jax.random.fold_in(codec_key, mx["serial"])
                # this merge's host-drawn unit-noise tree rides the xs
                # row (replayed from the SAME per-serial draws the eager
                # merge executor makes); absent on the no-noise path
                ns = mx["noise"] if privacy is not None else None
                Z, W, Hn = merge_contribution(
                    stc.Z, stc.W, Hcc, tz2, tw2, mx["slot"], mx["client"],
                    mx["gamma"], ckey, ns, codec=codec, ef=ef,
                    privacy=privacy)
                mv = mx["valid"]
                stn = stc._replace(Z=tree_where(mv, Z, stc.Z),
                                   W=tree_where(mv, W, stc.W))
                return (stn, tree_where(mv, Hn, Hcc)), jnp.zeros((),
                                                                 jnp.int32)

            (st3, H2), _ = jax.lax.scan(mbody, (st2, Hc), x["merges"])
            return (st3, H2, tz2, tw2, ws), rm

        carry, rms = jax.lax.scan(body, (state, H, tz, tw, ws), xs)
        return carry + (rms,)

    return jax.jit(chunk, donate_argnums=(0, 1, 2, 3, 4))


def _record_replay_chunk(sim: FedSim, C: int, collect_w_tau: bool,
                         table: _AsyncTable,
                         w_parts: list | None) -> list[SimMetrics]:
    """Record C async aggregation events, then replay them compiled."""
    rec = _RecordAsyncExec(_CandStream(sim), table)
    # contributions dispatched by an earlier EAGER phase enter the table:
    # their gathered batch rows become table rows (exact copies), so the
    # chunk program merges them like any recorded fire's upload
    for _, _, kind, c in sim._events:
        if kind == _EV_UPLOAD and c.slot < 0 and not c.dup:
            # (duplicate ghosts carry no payload at all -- dedup discards
            # them at arrival, so they never need a table row)
            s = table.alloc()
            table.z = tmap(lambda t, b: t.at[s].set(b[c.row]),
                           table.z, c.z_batch)
            table.w = tmap(lambda t, b: t.at[s].set(b[c.row]),
                           table.w, c.w_batch)
            c.slot, c.z_batch, c.w_batch = s, None, None

    sim._exec = rec
    try:
        mets = []
        with TraceAnnotation("repro.engine.async_record",
                             round=sim.round_idx):
            for t in range(C):
                rec.cur_step = t
                mets.append(sim.step())
    finally:
        sim._exec = _EAGER_ASYNC_EXEC

    fire_steps = {op["step"] for op in rec.ops if op["kind"] == 0}
    entry_w = None
    if collect_w_tau and len(fire_steps) < C:
        # steps without a fire keep the previous broadcast: their stack
        # rows forward-fill host-side, seeded from the chunk-entry w_tau
        # -- fetched BEFORE the donating call consumes it
        entry_w = np.asarray(jax.device_get(sim.state.w_tau))
        sim.host_syncs += 1

    w_np = None
    if rec.ops:
        cap, m = table.cap, sim.cfg.m
        # group the flat op stream: one program step per dispatch, each
        # carrying the merges recorded before the NEXT dispatch (a leading
        # merge-only prefix becomes one fire-invalid step)
        groups: list[dict] = []
        for op in rec.ops:
            if op["kind"] == 0:
                groups.append({"fire": op, "merges": []})
            else:
                if not groups:
                    groups.append({"fire": None, "merges": []})
                groups[-1]["merges"].append(op)
        n_steps = len(groups)
        # steps run a full (possibly discarded) round each, so pad to
        # SMALL buckets: pow2 up to 8, then multiples of 8 -- bounded
        # recompiles, bounded padding waste
        if n_steps <= 8:
            n_pad = 1 << max(0, (n_steps - 1).bit_length())
        else:
            n_pad = -(-n_steps // 8) * 8
        mmax = max((len(g["merges"]) for g in groups), default=0)
        m_pad = (1 << max(0, (mmax - 1).bit_length())) if mmax else 0

        fire_valid = np.zeros(n_pad, bool)
        mask = np.zeros((n_pad, m), bool)
        agg = np.zeros((n_pad, m), bool)
        slot_src = np.full((n_pad, cap), -1, np.int32)
        step = np.zeros(n_pad, np.int32)
        mvalid = np.zeros((n_pad, m_pad), bool)
        mslot = np.zeros((n_pad, m_pad), np.int32)
        mclient = np.zeros((n_pad, m_pad), np.int32)
        mserial = np.zeros((n_pad, m_pad), np.int32)
        mgamma = np.zeros((n_pad, m_pad), np.float32)
        last_fire = -1
        for i, g in enumerate(groups):
            if g["fire"] is not None:
                op = g["fire"]
                fire_valid[i] = True
                mask[i] = op["mask"]
                agg[i] = op["agg"]
                step[i] = op["step"]
                for s, cl in op["slots"]:
                    slot_src[i, s] = cl
                last_fire = i
            for j, op in enumerate(g["merges"]):
                mvalid[i, j] = True
                mslot[i, j] = op["slot"]
                mclient[i, j] = op["client"]
                mserial[i, j] = op["serial"]
                mgamma[i, j] = op["gamma"]
        fn = _async_chunk_fn(sim, collect_w_tau)
        H = sim._H if sim._ef else jnp.zeros((), jnp.float32)
        if collect_w_tau:
            ws0 = tmap(lambda v: jnp.zeros((C,) + v.shape, v.dtype),
                       sim.state.w_tau)
        else:
            ws0 = jnp.zeros((), jnp.float32)
        merges_x = {"valid": jnp.asarray(mvalid),
                    "slot": jnp.asarray(mslot),
                    "client": jnp.asarray(mclient),
                    "serial": jnp.asarray(mserial),
                    "gamma": jnp.asarray(mgamma)}
        if sim._privacy_tx is not None:
            # per-merge unit noise replayed from the SAME standalone
            # program (and the same per-serial key folds) the eager merge
            # executor uses, stacked to (n_pad, m_pad, 1, ...) xs rows;
            # invalid/padded merge slots carry zeros (their merges are
            # masked off, the values never land)
            like = sim._noise_row_like
            zero = tmap(lambda sd: jnp.zeros(sd.shape, sd.dtype), like)
            with TraceAnnotation("repro.engine.noise", round=sim.round_idx):
                flat = [draw_unit_noise(
                    jax.random.fold_in(sim._privacy_key,
                                       int(mserial[i, j])),
                    like, sim._privacy_tx) if mvalid[i, j] else zero
                    for i in range(n_pad) for j in range(m_pad)]
                if flat:
                    merges_x["noise"] = tmap(
                        lambda *ls: jnp.stack(ls).reshape(
                            (n_pad, m_pad) + ls[0].shape), *flat)
                else:
                    merges_x["noise"] = tmap(
                        lambda sd: jnp.zeros((n_pad, m_pad) + sd.shape,
                                             sd.dtype), like)
        xs = {"fire_valid": jnp.asarray(fire_valid),
              "mask": jnp.asarray(mask), "agg": jnp.asarray(agg),
              "slot_src": jnp.asarray(slot_src), "step": jnp.asarray(step),
              "merges": merges_x}
        with TraceAnnotation("repro.engine.async_replay",
                             round=sim.round_idx):
            state, H, tz, tw, ws, rms = fn(sim.state, H, table.z, table.w,
                                           ws0, sim._codec_key, xs)
            sim.state = state
            if sim._ef:
                sim._H = H
            table.z, table.w = tz, tw
            if last_fire >= 0:
                sim.last_round_metrics = tmap(lambda y: y[last_fire], rms)
            if collect_w_tau:
                w_np = np.asarray(jax.device_get(ws))
                sim.host_syncs += 1

    # in-flight table-backed contributions now reference the NEW table
    # trees (the old ones were donated into the chunk program)
    for _, _, kind_, c in sim._events:
        if kind_ == _EV_UPLOAD and c.slot >= 0:
            c.z_batch, c.w_batch, c.row = table.z, table.w, c.slot

    if collect_w_tau:
        rows, last = [], entry_w
        for t in range(C):
            if w_np is not None and t in fire_steps:
                last = w_np[t]
            rows.append(last)
        w_parts.append(np.stack(rows))
    return mets


def _run_async_scan(sim: FedSim, rounds: int, *, chunk: int | None,
                    collect_w_tau: bool,
                    event_table_capacity: int | None) -> EngineResult:
    chunk = rounds if chunk is None else min(chunk, rounds)
    # donation invariant: copy the entry state once (the caller may still
    # hold the s0 it passed to FedSim); later states are engine-owned
    sim.state = _copy_tree(sim.state)
    if sim._async_table is None:
        if event_table_capacity is not None:
            cap, fixed = int(event_table_capacity), True
        else:
            # capped: at most max_concurrency in flight + a buffer's worth
            # awaiting merge; uncapped: the pump tops the system up to one
            # cohort, so ~2 cohorts bounds it (growth covers the tail)
            conc = sim._max_conc if math.isfinite(sim._max_conc) \
                else 2 * sim._cohort
            cap, fixed = int(conc) + sim._buffer_k, False
        sim._async_table = _AsyncTable(sim.state.Z, sim.state.W,
                                       max(1, cap), fixed=fixed)
    table = sim._async_table
    mets: list[SimMetrics] = []
    w_parts: list[np.ndarray] | None = [] if collect_w_tau else None
    done = 0
    while done < rounds:
        C = min(chunk, rounds - done)
        mets += _record_replay_chunk(sim, C, collect_w_tau, table, w_parts)
        done += C
    return EngineResult(
        mets, np.concatenate(w_parts) if collect_w_tau else None)


# ---------------------------------------------------------------------------
# client-axis mesh sharding
# ---------------------------------------------------------------------------

def _resolve_mesh(mesh):
    """None | int | jax.sharding.Mesh -> Mesh or None.

    An int builds a (data=mesh, model=1) test mesh via launch.mesh
    (imported lazily -- the sim layer must not depend on launch at module
    load).
    """
    if mesh is None or hasattr(mesh, "axis_names"):
        return mesh
    from repro.launch.mesh import make_test_mesh
    return make_test_mesh(n_data=int(mesh), n_model=1)


def _client_sharded(tree, m: int, mesh):
    """device_put: leading-client-axis leaves shard over the mesh's data
    axis (the repo's single-pod logical rule client -> data with
    specs.leaf_spec's divisibility rails); other leaves replicate. On a
    single-device mesh this is semantically a no-op -- which is what pins
    sharded == unsharded bit-for-bit (tests/test_sim_invariants.py).
    """
    from repro.sharding.rules import single_pod_rules
    from repro.sharding.specs import leaf_spec
    rules = single_pod_rules()
    rep = jax.sharding.NamedSharding(mesh, jax.sharding.PartitionSpec())

    def put(x):
        if getattr(x, "ndim", 0) and x.shape[0] == m:
            spec = leaf_spec(("client",) + (None,) * (x.ndim - 1),
                             x.shape, mesh, rules)
            return jax.device_put(x, jax.sharding.NamedSharding(mesh, spec))
        return jax.device_put(x, rep)

    return tmap(put, tree)


# ---------------------------------------------------------------------------
# public entry point
# ---------------------------------------------------------------------------

def _chunk_args(sim: FedSim, H, masks, abandoned, noise) -> tuple:
    """Arguments of the clocked chunk for the next ``len(abandoned)``
    rounds of ``sim``: the one place ``run_rounds`` and ``lower_rounds``
    build them."""
    ridx0 = sim.round_idx
    return (sim.state, H, sim._codec_key, jnp.asarray(masks),
            jnp.asarray(abandoned),
            jnp.arange(ridx0, ridx0 + len(abandoned), dtype=jnp.int32),
            noise)


def run_rounds(sim: FedSim, rounds: int, *, chunk: int | None = None,
               collect_w_tau: bool = False, mesh=None,
               event_table_capacity: int | None = None) -> EngineResult:
    """Advance ``sim`` by ``rounds`` rounds via the fused scan engine.

    Drop-in replacement for ``sim.run(rounds)``: ``sim.state``, ``sim.t``,
    ``sim.metrics``, ``sim.ledger``, ``sim.round_idx`` and
    ``sim.last_round_metrics`` end up bit-identical to the eager driver's.
    ``chunk`` bounds the rounds compiled into one scan (default: all of
    ``rounds``; each distinct chunk length compiles once per FedSim).
    ``collect_w_tau=True`` additionally stacks every round's broadcast
    point on-device and returns it host-side -- O(rounds * n_params)
    memory, meant for objective evaluation on small tasks (the CLI), not
    for LM-scale states.

    The async policy runs the record/replay engine (module docstring):
    C aggregation events record through the shared scheduling pump, then
    replay as one compiled scan over the event table.
    ``event_table_capacity`` (async only) pins the table size -- overflow
    then raises instead of growing. ``mesh`` (None | int | Mesh) shards
    the client axis of the state over the mesh's "data" axis before the
    compiled chunks run; an int n builds an (n, 1) test mesh. A
    single-device mesh is bit-identical to no mesh.
    """
    if rounds < 1:
        raise ValueError(f"rounds must be >= 1; got {rounds}")
    if chunk is not None and chunk < 1:
        raise ValueError(f"chunk must be >= 1 (None = all rounds in one "
                         f"scan); got {chunk}")
    if event_table_capacity is not None and event_table_capacity < 1:
        raise ValueError(f"event_table_capacity must be >= 1; "
                         f"got {event_table_capacity}")
    mesh = _resolve_mesh(mesh)
    if mesh is not None:
        sim.state = _client_sharded(sim.state, sim.cfg.m, mesh)
        if sim._ef:
            sim._H = _client_sharded(sim._H, sim.cfg.m, mesh)
    if sim.sim.policy == "async":
        return _run_async_scan(sim, rounds, chunk=chunk,
                               collect_w_tau=collect_w_tau,
                               event_table_capacity=event_table_capacity)
    if event_table_capacity is not None:
        raise ValueError("event_table_capacity is owned by policy='async'; "
                         f"policy is {sim.sim.policy!r}")
    if sim.sim.policy not in _SCAN_POLICIES:
        raise ValueError(f"unknown policy {sim.sim.policy!r}")

    cand_stream = _candidate_stream_fn(sim)
    chunk_fn = _chunk_fn(sim, collect_w_tau)

    # donation invariant: snapshot the entry state once so buffers the
    # caller may still reference are never donated; all later chunk states
    # are engine-owned
    sim.state = _copy_tree(sim.state)
    H = _copy_tree(sim._H) if sim._ef else jnp.zeros((), jnp.float32)

    chunk = rounds if chunk is None else min(chunk, rounds)
    out_metrics: list[SimMetrics] = []
    w_parts: list[np.ndarray] = []
    done = 0
    while done < rounds:
        C = min(chunk, rounds - done)
        ridx0 = sim.round_idx
        # 1. arrivals: same host-RNG stream as C eager steps
        with TraceAnnotation("repro.engine.arrivals", round=ridx0):
            arrivals = np.stack([
                simclients.round_arrivals(
                    sim.profiles, sim._rng, sim._latency,
                    work_flops=sim._work, down_bytes=sim._down_bytes,
                    up_bytes=sim._up_bytes)
                for _ in range(C)])
        # 2./3. candidate-stream + policy replay to the abandoned fixpoint
        ewma0 = sim.deadlines.ewma.copy() \
            if sim.sim.policy == "adaptive" else None
        # the fault model's stream/quarantine state rewinds with each pass
        # (exactly the EWMA pattern above): every pass replays the chunk's
        # fault decisions from the same point, and the state the LAST pass
        # leaves behind is what C eager steps would have left
        fstate0 = sim._faults.state_snapshot() \
            if sim._faults is not None else None
        abandoned = np.zeros(C, bool)
        for _ in range(C + 1):
            with TraceAnnotation("repro.engine.candidates", round=ridx0):
                cands = np.asarray(cand_stream(
                    sim.state.key, sim.state.k, jnp.asarray(abandoned)))
            sim.host_syncs += 1
            if ewma0 is not None:
                sim.deadlines.ewma = ewma0.copy()
            if fstate0 is not None:
                sim._faults.state_restore(fstate0)
            with TraceAnnotation("repro.engine.policy", round=ridx0):
                (masks, durs, ab_new, rec_ups, cands_eff, arrs_eff,
                 fouts) = _policy_stream_host(sim, cands, arrivals)
            if np.array_equal(ab_new, abandoned):
                break
            abandoned = ab_new
        else:  # pragma: no cover - the prefix argument guarantees progress
            raise RuntimeError("abandoned-round fixpoint did not converge")
        # 4. one donated scan over the chunk
        if sim._privacy_tx is not None:
            # per-round unit noise, drawn host-side through the SAME
            # standalone program the eager step uses (one draw per round,
            # privacy stream folded on the round index), stacked as xs --
            # see transport.draw_unit_noise for why the draws must enter
            # the chunk as data rather than be computed in-body
            with TraceAnnotation("repro.engine.noise", round=ridx0):
                draws = [draw_unit_noise(
                    jax.random.fold_in(sim._privacy_key, r),
                    sim.state.Z, sim._privacy_tx)
                    for r in range(ridx0, ridx0 + C)]
                noise = tmap(lambda *ls: jnp.stack(ls), *draws)
        else:
            noise = None
        with TraceAnnotation("repro.engine.dispatch", round=ridx0):
            (sim.state, H), ys = chunk_fn(
                *_chunk_args(sim, H, masks, abandoned, noise))
            rm_stack = ys[0]
            if collect_w_tau:
                w_parts.append(np.asarray(jax.device_get(ys[1])))
                sim.host_syncs += 1

        # host bookkeeping, identical to C eager steps
        with TraceAnnotation("repro.engine.bookkeeping", round=ridx0):
            live = np.flatnonzero(~abandoned)
            if live.size:
                sim.last_round_metrics = tmap(
                    lambda y: y[int(live[-1])], rm_stack)
            for t in range(C):
                dur = float(durs[t])
                # the scan path reconstructs the SAME event stream the eager
                # step emits: same helper, same already-computed host arrays
                if sim.telemetry.enabled:
                    emit_clocked_round_events(
                        sim.telemetry, policy=sim.sim.policy,
                        round_idx=sim.round_idx, t0=sim.t,
                        candidates=cands_eff[t], arrivals=arrs_eff[t],
                        mask=masks[t], dur=dur, rec_up=rec_ups[t],
                        abandoned=bool(abandoned[t]), codec=sim.sim.codec,
                        up_bytes=sim._up_bytes, faults=fouts[t])
                apply_clocked_privacy(
                    sim._privacy, sim.telemetry, round_idx=sim.round_idx,
                    t_end=sim.t + dur, mask=masks[t], rec_up=rec_ups[t],
                    faults=fouts[t])
                if fouts[t] is None:
                    brec = sim.ledger.record_round(
                        down_mask=cands_eff[t], up_mask=rec_ups[t],
                        down_bytes=sim._down_bytes, up_bytes=sim._up_bytes,
                        ts=sim.t + dur, round_idx=sim.round_idx)
                else:
                    # same count-path billing as the eager step: delivered
                    # uploads + failed attempts + discarded duplicates
                    brec = sim.ledger.record_counts(
                        down_counts=cands_eff[t].astype(np.int64),
                        up_counts=rec_ups[t].astype(np.int64)
                        + fouts[t].extra_up,
                        down_bytes=sim._down_bytes, up_bytes=sim._up_bytes,
                        ts=sim.t + dur, round_idx=sim.round_idx)
                sim.t += dur
                m = make_sim_metrics(
                    round_idx=sim.round_idx, t_round=dur, t_total=sim.t,
                    n_contacted=int(cands_eff[t].sum()),
                    n_aggregated=int(masks[t].sum()), brec=brec,
                    abandoned=bool(abandoned[t]))
                sim.metrics.append(m)
                out_metrics.append(m)
                sim.round_idx += 1
        done += C
    if sim._ef:
        sim._H = H
    return EngineResult(
        out_metrics, np.concatenate(w_parts) if collect_w_tau else None)


def lower_rounds(sim: FedSim, rounds: int, *,
                 collect_w_tau: bool = False):
    """Lower, without running, the clocked chunk ``run_rounds`` compiles
    for ``rounds`` rounds of ``sim`` (with ``collect_w_tau`` as
    ``run_rounds`` is given it) -> ``jax.stages.Lowered``.

    ``.compile()`` on the result gives the program's compile time and its
    ``memory_analysis()`` before any device memory is spent on it, and its
    ``as_text()`` the op metadata a profiler trace's op names map to.
    Clocked policies without upload privacy (whose noise stack is
    host-drawn).
    """
    if sim.sim.policy not in _SCAN_POLICIES or sim._privacy_tx is not None:
        raise ValueError("lower_rounds covers the clocked policies without "
                         f"upload privacy; policy is {sim.sim.policy!r}")
    H = sim._H if sim._ef else jnp.zeros((), jnp.float32)
    return _chunk_fn(sim, collect_w_tau).lower(*_chunk_args(
        sim, H, np.ones((rounds, sim.cfg.m), bool), np.zeros(rounds, bool),
        None))


def run_to_objective(sim: FedSim, objective_fn, target: float, *,
                     max_rounds: int, chunk: int = 16) -> tuple:
    """Scan-engine race helper: run until the objective reaches ``target``.

    ``objective_fn`` maps the stacked (C, ...) per-round broadcast points
    to a (C,) vector of objective values -- ONE evaluation per chunk, so
    objective monitoring costs one dispatch per chunk instead of one per
    round (a per-round host ``float(f(w))`` would hand the dispatch
    overhead the engine removed straight back). Returns
    (rounds_to_target, hit: bool, objective at that round).
    """
    total = 0
    f = math.inf
    while total < max_rounds:
        C = min(chunk, max_rounds - total)
        res = run_rounds(sim, C, collect_w_tau=True)
        fs = np.asarray(objective_fn(jnp.asarray(res.w_tau)))
        sim.host_syncs += 1
        for fv in fs:
            total += 1
            f = float(fv)
            if f <= target:
                return total, True, f
    return total, False, f
