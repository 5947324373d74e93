"""Byte-accurate communication accounting and the optional upload codec.

Byte ledger
-----------
Wire sizes are derived from the REAL pytree leaf dtypes/shapes of the state
being exchanged (not a hand-waved parameter count): the server->client
broadcast moves one dense copy of w^{tau+1} per contacted client, the
client->server upload moves one (possibly encoded) copy of z_i per client
whose upload completed within the round. ``ByteLedger`` accumulates both
per round and per client, host-side -- in INTEGER units wherever the wire
size is exact (dense and whole-byte quantized payloads), falling back to
float only for fractional sizes (sub-byte bit-packing, top-k index
estimates), so long simulations cannot drift. Wire-size computations are
memoized per (treedef, leaf shapes, codec): repeated calls stop re-walking
the pytree.

Upload codec (top-k sparsification + uniform stochastic quantization)
---------------------------------------------------------------------
``codec_roundtrip`` models what the server RECEIVES when clients compress
uploads: per leaf, each client keeps the top ceil(topk_frac * n) coordinates
by magnitude, snaps the kept values onto a ``bits``-bit uniform grid
(repro.kernels.quant -- Pallas kernel with a bit-identical jnp reference),
and the server dequantizes BEFORE aggregation, substituting the client's
previous upload z_i^{tau-1} on dropped coordinates. ENS then runs on dense
dequantized uploads, so compressed FedEPM keeps the aggregation math of
core/fedepm.py unchanged: with bits=0 the kept coordinates are transmitted
exactly, and with topk_frac=1, bits=0 the codec is the identity. Dropped
coordinates are a per-coordinate analogue of the paper's eq. (22)
carry-through (the server reuses the stalest value it holds).

Batched multi-leaf encode (PR 4)
--------------------------------
The round-trip no longer loops leaf by leaf. Every (leaf, client) pair
becomes one row of a single padded 2-D array (leaves grouped by dtype,
padded to the group's widest flat leaf), so a whole pytree encodes in ONE
top-k + ONE fused ``quantize_cols`` kernel launch (kernels/quant/batch.py;
column-bounded: row i quantizes its leading kcols[i] live columns and
passes the fallback through elsewhere). The padded layout -- per-leaf keep
counts, row offsets -- is planned once per (treedef, leaf shapes, codec)
and cached. The dither stream is drawn per GROUP over the padded layout,
so compressed values differ from the pre-batched per-leaf stream in the
last stochastic bit; all codec laws (unbiasedness, error bounds, exact
top-k touch counts) are unchanged and pinned by tests.

Wire format accounted per client per leaf (n coords, k kept):
    dense  (k == n):  n * bits/8 payload + 4 B scale
    sparse (k <  n):  k * bits/8 payload + k * index_bytes + 4 B scale
with bits=0 meaning raw leaf-dtype values (no scale overhead when dense).

Error feedback (``CodecConfig.error_feedback`` + ``ef_roundtrip``)
------------------------------------------------------------------
The memoryless round-trip above silently BIASES the eq. (22) update: the
dropped/rounded-away part of every upload is lost each round. With error
feedback, client and server share a codec memory h_i; the wire carries
C(z_i - h_i) and both sides accumulate h_i <- h_i + C(z_i - h_i)
(kernels/quant fused ``ef_accumulate`` pair, run over the same stacked
multi-leaf rows), so compressed trajectories converge to the uncompressed
objective (tests/test_sim_async.py pins the contraction). Same wire
format, same byte accounting.
"""
from __future__ import annotations

import dataclasses
import functools
import math
from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.kernels.quant import ops as quant_ops
from repro.kernels.quant.ref import laplace_from_u32
from repro.telemetry.events import NULL_RECORDER

tmap = jax.tree_util.tree_map


# ---------------------------------------------------------------------------
# byte accounting
# ---------------------------------------------------------------------------

def _leaf_meta(leaves) -> tuple:
    """Hashable (shape, dtype) signature of a flattened pytree."""
    return tuple((tuple(x.shape), str(x.dtype)) for x in leaves)


# wire-size memos: keyed by (treedef, leaf signature[, codec]) -- a process
# touches a handful of state trees, so these stay tiny, but each hit saves
# a full pytree walk on the dispatch path
_DENSE_BYTES_CACHE: dict = {}
_STACKED_BYTES_CACHE: dict = {}
_ENCODED_BYTES_CACHE: dict = {}


def tree_client_bytes(tree) -> int:
    """Dense wire bytes of ONE client's pytree (leaves without client axis)."""
    leaves, treedef = jax.tree_util.tree_flatten(tree)
    key = (treedef, _leaf_meta(leaves))
    got = _DENSE_BYTES_CACHE.get(key)
    if got is None:
        got = _DENSE_BYTES_CACHE[key] = sum(
            x.size * x.dtype.itemsize for x in leaves)
    return got


def stacked_client_bytes(tree) -> int:
    """Dense wire bytes of ONE client's slice of a stacked (m, ...) pytree."""
    leaves, treedef = jax.tree_util.tree_flatten(tree)
    key = (treedef, _leaf_meta(leaves))
    got = _STACKED_BYTES_CACHE.get(key)
    if got is None:
        got = _STACKED_BYTES_CACHE[key] = sum(
            (x.size // x.shape[0]) * x.dtype.itemsize for x in leaves)
    return got


@dataclasses.dataclass(frozen=True)
class CodecConfig:
    """Upload compression: keep top-k by magnitude, quantize kept values.

    topk_frac: fraction of each leaf's coordinates kept (1.0 = dense).
    bits: wire bits per kept value (>= 2), or 0 to send kept values raw.
    stochastic: unbiased dithered rounding (True) vs round-half-up.
    impl: quantizer implementation, "ref" (jnp) or "pallas".
    index_bytes: per-kept-coordinate index cost when sparse (k < n).
    error_feedback: EF21-style codec memory -- compress the RESIDUAL
        against a shared reconstruction h_i instead of z_i itself (see
        ``ef_roundtrip``). Wire format and byte accounting are unchanged.
    """

    topk_frac: float = 1.0
    bits: int = 8
    stochastic: bool = True
    impl: str = "ref"
    index_bytes: int = 4
    error_feedback: bool = False

    def __post_init__(self):
        if not (0.0 < self.topk_frac <= 1.0):
            raise ValueError(f"topk_frac must be in (0, 1]; got {self.topk_frac}")
        if self.bits != 0 and self.bits < 2:
            raise ValueError(f"bits must be 0 (raw) or >= 2; got {self.bits}")


def _leaf_k(n: int, frac: float) -> int:
    return n if frac >= 1.0 else max(1, math.ceil(frac * n))


def encoded_client_bytes(tree, codec: CodecConfig | None) -> float:
    """Wire bytes of ONE client's (possibly encoded) upload of a stacked tree.

    Memoized per (treedef, leaf shapes/dtypes, codec). FedSim snapshots
    this size once per construction -- the per-dispatch billing uses that
    float -- so the memo pays off where sims are built in bulk over the
    same trees (benchmark grids, test suites) and where trees have many
    leaves (LM-scale states), not on the round hot path.
    """
    if codec is None:
        return float(stacked_client_bytes(tree))
    leaves, treedef = jax.tree_util.tree_flatten(tree)
    key = (treedef, _leaf_meta(leaves), codec)
    got = _ENCODED_BYTES_CACHE.get(key)
    if got is not None:
        return got
    total = 0.0
    for x in leaves:
        n = x.size // x.shape[0]
        k = _leaf_k(n, codec.topk_frac)
        payload = k * (codec.bits / 8.0 if codec.bits else x.dtype.itemsize)
        index = 0.0 if k == n else k * codec.index_bytes
        scale = 4.0 if codec.bits else (0.0 if k == n else 4.0)
        total += payload + index + scale
    _ENCODED_BYTES_CACHE[key] = total
    return total


def codec_event_attrs(codec: CodecConfig, *, n_clients: int,
                      up_bytes) -> dict:
    """Attrs dict for a telemetry ``codec_encode`` event."""
    return {"clients": int(n_clients),
            "bytes": float(up_bytes) * int(n_clients),
            "topk_frac": codec.topk_frac, "bits": codec.bits,
            "error_feedback": codec.error_feedback}


class LedgerSnapshot(NamedTuple):
    """O(1) running-total snapshot of a :class:`ByteLedger`.

    Integer and float accumulators are kept separate so deltas between two
    snapshots are exact on the integer paths (no float cancellation).
    """

    up_i: int
    down_i: int
    up_f: float
    down_f: float

    @property
    def up(self) -> float:
        return float(self.up_i + self.up_f)

    @property
    def down(self) -> float:
        return float(self.down_i + self.down_f)


class ByteLedger:
    """Per-round, per-client cumulative communication record (host-side).

    Per-client byte counters accumulate in int64 whenever the per-transfer
    wire size is a whole number of bytes (dense trees, whole-byte quantized
    payloads) and in float64 only otherwise (sub-byte packing / fractional
    top-k estimates), so integer-exact paths cannot accumulate float
    rounding drift over long runs. ``up``/``down`` expose the combined
    float64 view; totals are bit-identical to the all-float accumulation
    for every size below 2^53.

    Scalar running totals are maintained alongside the per-client arrays,
    so ``total_up``/``total_down`` and ``snapshot()``/``delta()`` are O(1)
    -- consumers (telemetry counters, run summaries) no longer re-sum the
    (m,) arrays each round. With a telemetry recorder attached, every
    record call that carries a ``ts`` emits a ``ledger_record`` event with
    the round's byte delta and the running totals.
    """

    def __init__(self, m: int, *, telemetry=None):
        self.m = m
        self.telemetry = NULL_RECORDER if telemetry is None else telemetry
        self._up_i = np.zeros(m, np.int64)
        self._down_i = np.zeros(m, np.int64)
        self._up_f = np.zeros(m, np.float64)
        self._down_f = np.zeros(m, np.float64)
        self._tot_up_i = 0
        self._tot_down_i = 0
        self._tot_up_f = 0.0
        self._tot_down_f = 0.0
        self.rounds: list[dict] = []

    @property
    def up(self) -> np.ndarray:
        """(m,) cumulative uplink bytes per client (float64 view)."""
        return self._up_i + self._up_f

    @property
    def down(self) -> np.ndarray:
        """(m,) cumulative downlink bytes per client (float64 view)."""
        return self._down_i + self._down_f

    def record_round(self, *, down_mask: np.ndarray, up_mask: np.ndarray,
                     down_bytes: float, up_bytes, ts: float | None = None,
                     round_idx: int | None = None) -> dict:
        """down_mask: clients the server contacted (they receive the
        broadcast); up_mask: clients whose upload completed; up_bytes:
        scalar or (m,) per-client encoded size."""
        return self.record_counts(
            down_counts=np.asarray(down_mask, bool).astype(np.int64),
            up_counts=np.asarray(up_mask, bool).astype(np.int64),
            down_bytes=down_bytes, up_bytes=up_bytes, ts=ts,
            round_idx=round_idx)

    def record_counts(self, *, down_counts: np.ndarray,
                      up_counts: np.ndarray, down_bytes: float,
                      up_bytes, ts: float | None = None,
                      round_idx: int | None = None) -> dict:
        """Count-based variant for the async server: one aggregation event
        may contact or receive from the same client several times (a client
        can sit in two overlapping cohorts), so transfers are integer COUNTS
        per client rather than boolean masks. n_down/n_up report distinct
        clients; the byte totals weight by the counts.

        ``ts``/``round_idx`` tag the telemetry ``ledger_record`` event
        (simulated time); omitted, the record is silent even with a
        recorder attached."""
        down_counts = np.asarray(down_counts, np.int64)
        up_counts = np.asarray(up_counts, np.int64)
        up_pc = np.broadcast_to(np.asarray(up_bytes, np.float64), (self.m,))
        d = down_counts * float(down_bytes)
        u = up_counts * up_pc
        if float(down_bytes).is_integer():
            di = down_counts * np.int64(down_bytes)
            self._down_i += di
            self._tot_down_i += int(di.sum())
        else:
            self._down_f += d
            self._tot_down_f += float(d.sum())
        if np.all(up_pc == np.floor(up_pc)):
            ui = up_counts * up_pc.astype(np.int64)
            self._up_i += ui
            self._tot_up_i += int(ui.sum())
        else:
            self._up_f += u
            self._tot_up_f += float(u.sum())
        rec = {"round": len(self.rounds), "down": float(d.sum()),
               "up": float(u.sum()), "n_down": int((down_counts > 0).sum()),
               "n_up": int((up_counts > 0).sum())}
        self.rounds.append(rec)
        if self.telemetry.enabled and ts is not None:
            self.telemetry.event(
                "ledger_record", ts=ts,
                round_idx=len(self.rounds) - 1 if round_idx is None
                else round_idx,
                up=rec["up"], down=rec["down"], n_up=rec["n_up"],
                n_down=rec["n_down"], total_up=self.total_up,
                total_down=self.total_down)
        return rec

    def snapshot(self) -> LedgerSnapshot:
        """O(1) copy of the running totals (int/float paths separate)."""
        return LedgerSnapshot(up_i=self._tot_up_i, down_i=self._tot_down_i,
                              up_f=self._tot_up_f, down_f=self._tot_down_f)

    def checkpoint(self) -> dict:
        """Deep copy of the FULL ledger state (per-client arrays, totals,
        round records) -- the rewind anchor ``FedSim.snapshot()`` takes so a
        scan chunk that overshoots a termination rule can be replayed
        exactly. Unlike :meth:`snapshot`, this is O(m + rounds)."""
        return {"up_i": self._up_i.copy(), "down_i": self._down_i.copy(),
                "up_f": self._up_f.copy(), "down_f": self._down_f.copy(),
                "tot": (self._tot_up_i, self._tot_down_i,
                        self._tot_up_f, self._tot_down_f),
                "rounds": [dict(r) for r in self.rounds]}

    def restore(self, chk: dict) -> None:
        """Rewind to a :meth:`checkpoint` (the checkpoint stays reusable)."""
        self._up_i = chk["up_i"].copy()
        self._down_i = chk["down_i"].copy()
        self._up_f = chk["up_f"].copy()
        self._down_f = chk["down_f"].copy()
        (self._tot_up_i, self._tot_down_i,
         self._tot_up_f, self._tot_down_f) = chk["tot"]
        self.rounds = [dict(r) for r in chk["rounds"]]

    def delta(self, since: LedgerSnapshot) -> dict:
        """Bytes moved since ``since`` -- exact on the integer paths."""
        return {"up": float((self._tot_up_i - since.up_i)
                            + (self._tot_up_f - since.up_f)),
                "down": float((self._tot_down_i - since.down_i)
                              + (self._tot_down_f - since.down_f))}

    @property
    def total_up(self) -> float:
        return float(self._tot_up_i + self._tot_up_f)

    @property
    def total_down(self) -> float:
        return float(self._tot_down_i + self._tot_down_f)

    @property
    def total(self) -> float:
        return self.total_up + self.total_down


# ---------------------------------------------------------------------------
# batched multi-leaf encode plan (cached per treedef/shapes/codec)
# ---------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class _GroupPlan:
    """One dtype group of the padded 2-D layout.

    ``index``/``shape``/``n``/``k`` are per-leaf (flattened-tree position,
    stacked shape, flat coordinate count, keep count); rows of the stacked
    array are leaf-major: rows [l*m, (l+1)*m) belong to leaf l.
    """

    index: tuple[int, ...]
    shape: tuple[tuple[int, ...], ...]
    n: tuple[int, ...]
    k: tuple[int, ...]
    n_max: int
    k_max: int
    dense: bool       # every leaf keeps all coordinates (k == n)


_PLAN_CACHE: dict = {}


def _codec_plan(treedef, leaves, codec: CodecConfig) -> tuple[_GroupPlan, ...]:
    key = (treedef, _leaf_meta(leaves), codec)
    plan = _PLAN_CACHE.get(key)
    if plan is not None:
        return plan
    by_dtype: dict[str, list[int]] = {}
    for i, x in enumerate(leaves):
        by_dtype.setdefault(str(x.dtype), []).append(i)
    groups = []
    for idxs in by_dtype.values():
        ns = tuple(leaves[i].size // leaves[i].shape[0] for i in idxs)
        ks = tuple(_leaf_k(n, codec.topk_frac) for n in ns)
        groups.append(_GroupPlan(
            index=tuple(idxs),
            shape=tuple(tuple(leaves[i].shape) for i in idxs),
            n=ns, k=ks, n_max=max(ns), k_max=max(ks),
            dense=all(k == n for k, n in zip(ks, ns))))
    plan = _PLAN_CACHE[key] = tuple(groups)
    return plan


def _stack_rows(leaves, gp: _GroupPlan) -> jax.Array:
    """Group leaves -> (len(gp.index) * m, n_max) leaf-major row stack."""
    m = leaves[0].shape[0]
    rows = []
    for x, n in zip(leaves, gp.n):
        flat = x.reshape(m, -1)
        if n < gp.n_max:
            flat = jnp.pad(flat, ((0, 0), (0, gp.n_max - n)))
        rows.append(flat)
    return jnp.concatenate(rows, axis=0)


def _unstack_rows(rows: jax.Array, gp: _GroupPlan, m: int) -> list:
    return [rows[i * m:(i + 1) * m, :n].reshape(shape)
            for i, (n, shape) in enumerate(zip(gp.n, gp.shape))]


def _group_cols(gp: _GroupPlan, m: int):
    """Per-row live-coordinate and keep counts, (R,) int32 device consts."""
    ncols = jnp.asarray(np.repeat(np.asarray(gp.n, np.int32), m))
    kcols = jnp.asarray(np.repeat(np.asarray(gp.k, np.int32), m))
    return ncols, kcols


def _topk_rows(rows: jax.Array, live32: jax.Array, gp: _GroupPlan):
    """Top-k_max magnitudes per row over the live columns only.

    ``live32`` masks real coordinates (padding gets magnitude -1, so it is
    never selected while k <= n). lax.top_k sorts descending with ties
    broken by lowest index, so truncating a row to its leading k_l columns
    yields exactly that leaf's per-leaf top-k -- the same set the old
    leaf-by-leaf encode picked.
    """
    mag = jnp.where(live32, jnp.abs(rows.astype(jnp.float32)), -1.0)
    _, idx = jax.lax.top_k(mag, gp.k_max)
    return idx


# ---------------------------------------------------------------------------
# codec round-trip (what the server holds after dequantization)
# ---------------------------------------------------------------------------

def _codec_group(z_leaves, fb_leaves, key, codec: CodecConfig,
                 gp: _GroupPlan):
    """Fused round-trip of one dtype group; returns decoded leaves."""
    m = z_leaves[0].shape[0]
    if gp.dense and not codec.bits:
        return z_leaves  # every coordinate kept and sent raw: identity
    R = len(gp.index) * m
    z_rows = _stack_rows(z_leaves, gp)
    ncols, kcols = _group_cols(gp, m)

    if gp.dense:
        # no coordinate dropping: quantize the live columns in place (the
        # fallback operand passes padding through; it is sliced away)
        scale = jnp.max(jnp.abs(z_rows.astype(jnp.float32)), axis=1)
        u32 = (jax.random.bits(key, (R, gp.n_max), dtype=jnp.uint32)
               if codec.stochastic else None)
        out_rows = quant_ops.quantize_cols(z_rows, z_rows, scale, ncols,
                                           codec.bits, u32, impl=codec.impl)
        return _unstack_rows(out_rows, gp, m)

    fb_rows = _stack_rows(fb_leaves, gp)
    col_n = jnp.arange(gp.n_max, dtype=jnp.int32)[None, :]
    idx = _topk_rows(z_rows, col_n < ncols[:, None], gp)
    vals = jnp.take_along_axis(z_rows, idx, axis=1)       # (R, k_max)
    fbv = jnp.take_along_axis(fb_rows, idx, axis=1)       # (R, k_max)
    col_k = jnp.arange(gp.k_max, dtype=jnp.int32)[None, :]
    live = col_k < kcols[:, None]
    if codec.bits:
        scale = jnp.max(
            jnp.where(live, jnp.abs(vals.astype(jnp.float32)), 0.0), axis=1)
        u32 = (jax.random.bits(key, (R, gp.k_max), dtype=jnp.uint32)
               if codec.stochastic else None)
        enc = quant_ops.quantize_cols(vals, fbv, scale, kcols, codec.bits,
                                      u32, impl=codec.impl)
    else:
        enc = jnp.where(live, vals, fbv)
    # columns past a row's keep count scatter its fallback value back onto
    # its own index -- a no-op -- so one scatter serves every row width
    out_rows = jax.vmap(lambda f, i, v: f.at[i].set(v))(fb_rows, idx, enc)
    return _unstack_rows(out_rows, gp, m)


@jax.named_scope("upload_codec")
def codec_roundtrip(tree_z, tree_fallback, key: jax.Array,
                    codec: CodecConfig | None):
    """Encode + decode every client's upload; stacked (m, ...) pytrees.

    ``tree_fallback`` supplies dropped coordinates (the server's stale copy,
    normally the previous round's Z). Identity when codec is None. The
    whole pytree encodes through the fused multi-leaf path: one top-k and
    one ``quantize_cols`` launch per dtype group, not one of each per leaf.
    """
    if codec is None:
        return tree_z
    if codec.topk_frac >= 1.0 and not codec.bits:
        return tree_z  # identity codec
    leaves, treedef = jax.tree_util.tree_flatten(tree_z)
    fb_leaves = jax.tree_util.tree_leaves(tree_fallback)
    plan = _codec_plan(treedef, leaves, codec)
    keys = jax.random.split(key, len(plan))
    out = list(leaves)
    for gp, gkey in zip(plan, keys):
        dec = _codec_group([leaves[i] for i in gp.index],
                           [fb_leaves[i] for i in gp.index],
                           gkey, codec, gp)
        for i, leaf in zip(gp.index, dec):
            out[i] = leaf
    return jax.tree_util.tree_unflatten(treedef, out)


# ---------------------------------------------------------------------------
# error-feedback round-trip (EF21-style codec memory)
# ---------------------------------------------------------------------------

def _ef_group(z_leaves, h_leaves, key, codec: CodecConfig, gp: _GroupPlan):
    """Fused EF step of one dtype group; returns the new shared memories."""
    m = z_leaves[0].shape[0]
    if gp.dense and not codec.bits:
        # wire carries the full residual exactly: bit-exact identity
        # (h + (z - h) would re-associate in floating point)
        return z_leaves
    R = len(gp.index) * m
    z_rows = _stack_rows(z_leaves, gp)
    h_rows = _stack_rows(h_leaves, gp)
    ncols, kcols = _group_cols(gp, m)

    if gp.dense:
        # fused accumulate H + Q(Z - H) over the whole group's rows;
        # padding columns have z = h = 0, so they quantize to exactly 0
        r = z_rows - h_rows
        scale = jnp.max(jnp.abs(r.astype(jnp.float32)), axis=1)
        u32 = (jax.random.bits(key, (R, gp.n_max), dtype=jnp.uint32)
               if codec.stochastic else None)
        out_rows = quant_ops.ef_accumulate(z_rows, h_rows, scale,
                                           codec.bits, u32, impl=codec.impl)
        return _unstack_rows(out_rows, gp, m)

    r_rows = z_rows - h_rows
    col_n = jnp.arange(gp.n_max, dtype=jnp.int32)[None, :]
    idx = _topk_rows(r_rows, col_n < ncols[:, None], gp)
    vals = jnp.take_along_axis(r_rows, idx, axis=1)       # residual values
    col_k = jnp.arange(gp.k_max, dtype=jnp.int32)[None, :]
    live = col_k < kcols[:, None]
    if codec.bits:
        scale = jnp.max(
            jnp.where(live, jnp.abs(vals.astype(jnp.float32)), 0.0), axis=1)
        u32 = (jax.random.bits(key, (R, gp.k_max), dtype=jnp.uint32)
               if codec.stochastic else None)
        enc = quant_ops.quantize_cols(vals, jnp.zeros_like(vals), scale,
                                      kcols, codec.bits, u32,
                                      impl=codec.impl)
    else:
        enc = jnp.where(live, vals, jnp.zeros_like(vals))
    # accumulate the (zero-padded past each row's keep count) residual
    out_rows = jax.vmap(lambda h, i, v: h.at[i].add(v))(h_rows, idx, enc)
    return _unstack_rows(out_rows, gp, m)


@jax.named_scope("upload_ef")
def ef_roundtrip(tree_z, tree_h, key: jax.Array, codec: CodecConfig | None):
    """Error-feedback encode + decode; stacked (m, ...) pytrees.

    ``tree_h`` is the shared codec memory (the server's reconstruction after
    the client's last delivered upload; init all-zeros). Returns the NEW
    memory, which is also exactly what the server now holds for each client
    -- callers use it both as the decoded upload and as the next h. Identity
    when codec is None, and bit-exact identity for the dense raw codec
    (k == n, bits == 0): the wire then carries the residual exactly, so
    returning z avoids the h + (z - h) float re-association. Same fused
    multi-leaf layout as ``codec_roundtrip``.
    """
    if codec is None:
        return tree_z
    if codec.topk_frac >= 1.0 and not codec.bits:
        return tree_z  # dense raw residual: exact identity
    leaves, treedef = jax.tree_util.tree_flatten(tree_z)
    h_leaves = jax.tree_util.tree_leaves(tree_h)
    plan = _codec_plan(treedef, leaves, codec)
    keys = jax.random.split(key, len(plan))
    out = list(leaves)
    for gp, gkey in zip(plan, keys):
        dec = _ef_group([leaves[i] for i in gp.index],
                        [h_leaves[i] for i in gp.index],
                        gkey, codec, gp)
        for i, leaf in zip(gp.index, dec):
            out[i] = leaf
    return jax.tree_util.tree_unflatten(treedef, out)


# ---------------------------------------------------------------------------
# private round-trip (clip + DP noise in front of the codec)
# ---------------------------------------------------------------------------

def _gaussian_from_u32(u32: jax.Array) -> jax.Array:
    """Unit-scale Gaussian noise from uint32 bits via the inverse CDF.

    Counterpart of ``kernels.quant.ref.laplace_from_u32`` for the gaussian
    mechanism (sequential path only; the fused kernel is Laplace-only).
    The uniform is clamped away from {0, 1} so ndtri stays finite.
    """
    u = u32.astype(jnp.float32) * float(2.0 ** -32)
    u = jnp.clip(u, 1e-7, 1.0 - 1e-7)
    return jax.scipy.special.ndtri(u).astype(jnp.float32)


@functools.partial(jax.jit, static_argnames=("shapes", "mechanism"))
def _draw_noise_leaves(pkey: jax.Array, *, shapes, mechanism: str):
    """Standalone unit-noise program: one leaf of noise per shape.

    ``pkey`` splits per leaf in flatten order; each leaf's uint32 stream
    maps through the mechanism's inverse CDF. This is its OWN compiled
    program, never inlined into a merge or scan body -- see
    :func:`draw_unit_noise` for why that isolation is load-bearing.
    """
    keys = jax.random.split(pkey, len(shapes))
    out = []
    for shp, k in zip(shapes, keys):
        u32 = jax.random.bits(k, shp, dtype=jnp.uint32)
        out.append(laplace_from_u32(u32) if mechanism == "laplace"
                   else _gaussian_from_u32(u32))
    return out


def draw_unit_noise(pkey: jax.Array, tree_like, privacy):
    """Unit-scale DP noise tree (float32 leaves shaped like ``tree_like``).

    BOTH engines call this from the HOST and feed the result into their
    compiled merge programs as data, exactly like the policy mask streams
    and the quantizer dither planes. The hoisting is a bit-exactness
    requirement, not a convenience: the inverse-CDF transforms
    (``log1p``/``ndtri``) are transcendentals whose last-ulp rounding
    depends on how XLA:CPU vectorizes the fusion cluster they land in, so
    computing them INSIDE the eager merge program and again inside the
    scan chunk program yields values that differ by 1 ulp on some
    elements. Drawn here, the noise comes out of one shared program and
    enters every consumer as an unfusable input buffer, so eager and scan
    see bit-identical draws by construction.
    """
    leaves, treedef = jax.tree_util.tree_flatten(tree_like)
    shapes = tuple(tuple(x.shape) for x in leaves)
    ns = _draw_noise_leaves(pkey, shapes=shapes,
                            mechanism=privacy.mechanism)
    return jax.tree_util.tree_unflatten(treedef, ns)


def _client_l1(leaves, m: int) -> jax.Array:
    """(m,) per-client l1 norm over a stacked tree, float32.

    Summed leaf-by-leaf in flatten order, with the per-leaf row sum
    expressed as abs(x) @ ones rather than ``jnp.sum(axis=1)``. The dot
    form is a bit-exactness requirement, not a style choice: a fusible
    reduce's accumulation order depends on how XLA:CPU tiles the fusion
    it lands in (vectorized partial sums vs in-order scalar), so the
    same row summed inside the eager merge program and inside the scan
    chunk can differ in the last ulp -- and a 1-ulp l1 shift moves the
    clip factor and noise scale, which the trajectory then amplifies. A
    dot is emitted as its own computation over materialized operands in
    every context, so both engines accumulate identically.
    """
    tot = jnp.zeros((m,), jnp.float32)
    for x in leaves:
        a = jnp.abs(x.astype(jnp.float32)).reshape(m, -1)
        tot = tot + a @ jnp.ones((a.shape[1],), jnp.float32)
    return tot


def privacy_row_params(l1: jax.Array, privacy) -> tuple[jax.Array, jax.Array]:
    """Per-client (clip factor, noise scale) from the upload l1 norms.

    ``privacy`` is a ``repro.privacy.PrivacyConfig`` with ``eps > 0``.
    Surrogate mode uses the paper's data-dependent sensitivity
    ``delta_hat = 2 * ||z||_1`` (eq. 39) and never rescales the upload;
    clip mode first enforces ``||z||_1 <= clip`` (the same
    min(1, clip/||z||_1) factor as ``core.dp.clip_tree_l1``) and then
    uses the data-independent bound ``delta_hat = 2 * clip``. Laplace
    scale is ``b = delta_hat / eps``; the gaussian std multiplies in the
    standard ``sqrt(2 ln(1.25/delta))`` calibration (conservative here:
    ``||.||_2 <= ||.||_1`` so the l1 bound covers the l2 sensitivity).
    """
    if privacy.sensitivity == "clip":
        clipf = jnp.minimum(
            1.0, privacy.clip / jnp.maximum(l1, 1e-30)).astype(jnp.float32)
        delta_hat = jnp.full_like(l1, 2.0 * privacy.clip)
    else:
        clipf = jnp.ones_like(l1)
        delta_hat = 2.0 * l1
    b = delta_hat * (1.0 / privacy.eps)
    if privacy.mechanism == "gaussian":
        b = b * math.sqrt(2.0 * math.log(1.25 / privacy.delta))
    return clipf, b


def _clip_noise_tree(tree_z, noise, clipf: jax.Array, b: jax.Array):
    """Sequential clip + noise: z_i <- z_i * clipf_i + b_i * noise, per leaf.

    ``noise`` is the host-drawn unit-noise tree (:func:`draw_unit_noise`,
    shaped like ``tree_z``) -- an input buffer, never computed in-body,
    so both engines consume bit-identical draws.
    """
    leaves, treedef = jax.tree_util.tree_flatten(tree_z)
    n_leaves = jax.tree_util.tree_leaves(noise)
    out = []
    for x, n in zip(leaves, n_leaves):
        shp = (x.shape[0],) + (1,) * (x.ndim - 1)
        # barrier the clipped product: the affine has TWO products
        # feeding one add, and which of them XLA contracts into an FMA
        # depends on the surrounding program -- eager's merge program and
        # the scan chunk would round differently whenever clipf != 1.
        # Fencing x*clipf leaves b*n as the only contraction candidate,
        # so every context compiles the same fma(b, n, x*clipf).
        xc = jax.lax.optimization_barrier(
            x.astype(jnp.float32) * clipf.reshape(shp))
        y = xc + b.reshape(shp) * n
        out.append(y.astype(x.dtype))
    return jax.tree_util.tree_unflatten(treedef, out)


def _fused_private(leaves, treedef, key, noise, codec: CodecConfig,
                   clipf: jax.Array, b: jax.Array):
    """Dense-quantized Laplace path: ONE fused clip+noise+quantize launch
    per dtype group (kernels/quant private_quantize_cols)."""
    m = leaves[0].shape[0]
    n_leaves = jax.tree_util.tree_leaves(noise)
    plan = _codec_plan(treedef, leaves, codec)
    keys = jax.random.split(key, len(plan))
    out = list(leaves)
    for gp, gkey in zip(plan, keys):
        z_rows = _stack_rows([leaves[i] for i in gp.index], gp)
        # the host-drawn unit noise stacks into the same leaf-major row
        # layout as the values it perturbs (padding cols get zero noise;
        # they exit through the fallback select regardless)
        lap = _stack_rows([n_leaves[i] for i in gp.index], gp)
        ncols, _ = _group_cols(gp, m)
        R = len(gp.index) * m
        cf_r = jnp.tile(clipf, len(gp.index))
        b_r = jnp.tile(b, len(gp.index))
        # quantizer range covers the CLIPPED pre-noise magnitudes; noisy
        # outliers saturate at the grid edge (bounded-output DP). The
        # scale of a positive row is bit-identical to rowmax(|x * cf|):
        # multiplying by a nonnegative per-row constant is monotone even
        # in floating point.
        scale = jnp.max(jnp.abs(z_rows.astype(jnp.float32)), axis=1) * cf_r
        u32q = (jax.random.bits(gkey, (R, gp.n_max), dtype=jnp.uint32)
                if codec.stochastic
                else jnp.full((R, gp.n_max), 1 << 31, jnp.uint32))
        out_rows = quant_ops.private_quantize_cols(
            z_rows, z_rows, cf_r, b_r, scale, ncols, codec.bits, u32q,
            lap, impl=codec.impl)
        for i, leaf in zip(gp.index, _unstack_rows(out_rows, gp, m)):
            out[i] = leaf
    return jax.tree_util.tree_unflatten(treedef, out)


@jax.named_scope("upload_privacy")
def private_roundtrip(tree_z, tree_fallback, key: jax.Array,
                      noise, codec: CodecConfig | None, privacy):
    """Clip + DP-noise + codec round-trip; stacked (m, ...) pytrees.

    What the server receives from each client on the private upload path
    (docs/privacy.md): the upload is l1-clipped (clip mode) or taken as-is
    (surrogate mode), perturbed with per-client calibrated noise, then
    pushed through the ordinary codec. ``noise`` is the unit-noise tree
    the HOST drew with :func:`draw_unit_noise` from the dedicated privacy
    key stream (NEVER from the codec key) -- see that docstring for why
    the draws must enter as data. ``privacy`` is a
    ``repro.privacy.PrivacyConfig`` or None; with no noise to add (None
    or eps == 0) this IS ``codec_roundtrip``, bit-for-bit, and ``noise``
    is untouched (callers pass None).

    The dense quantized Laplace configuration -- the paper's mechanism
    under the default codec -- runs as ONE fused kernel launch per dtype
    group (clip + noise + quantize, ``kernels.quant.private_quantize_cols``
    with its quantizer range set by the clipped PRE-noise magnitudes);
    every other configuration (sparse top-k, raw bits=0, no codec,
    gaussian) applies the same clip+noise sequentially and lets the
    existing codec machinery finish the job.
    """
    if privacy is None or privacy.eps <= 0:
        return codec_roundtrip(tree_z, tree_fallback, key, codec)
    leaves, treedef = jax.tree_util.tree_flatten(tree_z)
    m = leaves[0].shape[0]
    clipf, b = privacy_row_params(_client_l1(leaves, m), privacy)
    if (codec is not None and codec.bits >= 2 and codec.topk_frac >= 1.0
            and privacy.mechanism == "laplace"):
        return _fused_private(leaves, treedef, key, noise, codec, clipf, b)
    noisy = _clip_noise_tree(tree_z, noise, clipf, b)
    return codec_roundtrip(noisy, tree_fallback, key, codec)


@jax.named_scope("upload_privacy")
def private_ef_roundtrip(tree_z, tree_h, key: jax.Array, noise,
                         codec: CodecConfig | None, privacy):
    """Error-feedback variant: EF compresses the NOISY upload's residual.

    Clip+noise runs sequentially in front (the EF accumulate consumes the
    residual against the shared memory h, so the fused quantizer -- whose
    range tracks the raw clipped upload -- does not apply), then
    ``ef_roundtrip`` proceeds unchanged: the codec memory contracts toward
    the noisy z, which is exactly the value the mechanism released. With
    no noise to add this IS ``ef_roundtrip``, bit-for-bit.
    """
    if privacy is None or privacy.eps <= 0:
        return ef_roundtrip(tree_z, tree_h, key, codec)
    leaves, _ = jax.tree_util.tree_flatten(tree_z)
    m = leaves[0].shape[0]
    clipf, b = privacy_row_params(_client_l1(leaves, m), privacy)
    noisy = _clip_noise_tree(tree_z, noise, clipf, b)
    return ef_roundtrip(noisy, tree_h, key, codec)
