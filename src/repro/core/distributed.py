"""Distributed FedEPM: the paper's Algorithm 2 as a first-class pjit
optimizer for large models on a TPU mesh (DESIGN.md §2).

Two execution strategies for the same algorithm (tests assert they agree
with the single-host reference to float tolerance):

**spatial** -- clients ARE device groups. The stacked client state
  (W, Z, g) carries a leading m axis sharded over the client mesh axes
  (("pod","data") multi-pod, ("data",) single-pod); feature axes shard over
  "model" (tensor parallel inside each client). Gradients for all clients
  run concurrently (vmap over the client axis). The server step (ENS, eq.
  (19)) is the only cross-client communication:
    * ``ens="gather"``  -- ENS along the m axis (selection or sort, see
      ``ens_gather``); XLA gathers the client-sharded axis (paper-faithful
      star transport: everyone's z to one place). O(m x n) bytes received
      per device group.
    * ``ens="a2a"``     -- beyond-paper: shard_map all_to_all redistributes
      coordinates so each device group owns n/m coordinates of ALL clients,
      runs ENS locally, and all-gathers the n/m-sized aggregate. O(n) bytes
      per device -- an m/2-fold collective saving (EXPERIMENTS.md §Perf).

**temporal** -- clients are time-multiplexed over the whole pod. Client
  state is coordinate-sharded over ("data","model") jointly (ZeRO-style;
  each leaf keeps its model sharding and gains an fsdp axis), the m axis is
  local, and clients take turns: a lax.scan computes grad f_i(w^tau) with
  the full mesh (batch data-parallel, params FSDP), then runs the k0
  elementwise prox steps (20). ENS becomes COLLECTIVE-FREE (every device
  holds all m values for its coordinates); the only collectives are the
  FSDP all-gathers/reduce-scatters of the gradient step. This is what lets
  a 141B mixtral-8x22b run FedEPM with m=4 on one v5e-256 pod.

The algorithmic semantics (selection, mu schedule, soft-threshold update,
DP noise scale, eq. (22) carry-through) are identical across strategies and
match core/fedepm.fedepm_round.
"""
from __future__ import annotations

import dataclasses
from functools import partial
from typing import Any, Callable, Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax import shard_map
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.core import dp
from repro.core.fedepm import (
    FedEPMConfig,
    FedEPMState,
    RoundMetrics,
    _client_inner,
    _select,
)
from repro.core.treeutil import tmap, tree_sq_norm, tree_where_client
from repro.kernels.ens import ops as ens_ops
from repro.models.logical import param_logical
from repro.sharding import specs as sh


@dataclasses.dataclass(frozen=True)
class DistConfig:
    mode: str = "spatial"            # "spatial" | "temporal"
    ens: str = "gather"              # "gather" | "a2a" (spatial only)
    client_axes: tuple = ("data",)   # mesh axes carrying the client axis
    fsdp_axes: tuple = ("data",)     # temporal: extra param sharding axes
    state_dtype: Any = None          # W/Z storage dtype (None = param dtype)
    remat: bool = True               # rematerialise the per-client loss
    microbatch: int = 1              # temporal: grad-accumulation chunks


# ---------------------------------------------------------------------------
# spec derivation
# ---------------------------------------------------------------------------

def param_specs(cfg_arch, abstract_params, mesh: Mesh, dist: DistConfig):
    """PartitionSpecs for ONE model copy (w_tau / serving params)."""
    logical = param_logical(cfg_arch)
    fsdp = dist.fsdp_axes if dist.mode == "temporal" else ()
    return sh.tree_specs(logical, abstract_params, mesh, fsdp_axes=fsdp)


def client_state_specs(cfg_arch, abstract_params, mesh: Mesh,
                       dist: DistConfig):
    """Specs for the stacked (m, ...) client state W/Z/g."""
    logical = param_logical(cfg_arch)
    if dist.mode == "spatial":
        return sh.tree_specs(logical, abstract_params, mesh,
                             prepend=(dist.client_axes if len(
                                 dist.client_axes) > 1 else
                                 dist.client_axes[0],))
    # temporal: m local; feature dims model+fsdp sharded
    return sh.tree_specs(logical, abstract_params, mesh,
                         fsdp_axes=dist.fsdp_axes, prepend=(None,))


def state_specs(cfg_arch, abstract_state: FedEPMState, mesh: Mesh,
                dist: DistConfig) -> FedEPMState:
    """FedEPMState pytree of PartitionSpecs (w_tau, W, Z, k, key).

    ``abstract_state.W/Z`` carry the stacked (m, ...) leaves so the
    divisibility checks in specs.leaf_spec see the true core shapes.
    """
    return FedEPMState(
        w_tau=param_specs(cfg_arch, abstract_state.w_tau, mesh, dist),
        W=client_state_specs(cfg_arch, abstract_state.W, mesh, dist),
        Z=client_state_specs(cfg_arch, abstract_state.Z, mesh, dist),
        k=P(),
        key=P(),
    )


def batch_specs(batch_tree, dist: DistConfig) -> Any:
    """Stacked client batches (m, b, ...): spatial shards m over client
    axes; temporal keeps m local and shards the inner batch dim."""
    ca = dist.client_axes if len(dist.client_axes) > 1 else \
        dist.client_axes[0]
    if dist.mode == "spatial":
        return tmap(lambda x: P(ca, *([None] * (x.ndim - 1))), batch_tree)
    return tmap(lambda x: P(None, ca, *([None] * (x.ndim - 2))), batch_tree)


# ---------------------------------------------------------------------------
# distributed ENS
# ---------------------------------------------------------------------------

def ens_gather(Z, lam, eta, local_impl: str = "ref"):
    """Baseline transport: ENS along the (client-sharded) m axis, leaf by
    leaf through ens_tree, and XLA moves the client rows between device
    groups -- the faithful analogue of every client uploading z_i to the
    server. With m <= 8 each leaf takes the min/max selection over the
    client rows (row slices of the sharded axis); above that it sorts
    along the axis, in lax.map chunks over axis 1 for large leaves so the
    (2m+1)-stacked sort buffers stay bounded (see kernels/ens/ops.py).
    """
    return ens_ops.ens_tree(Z, lam, eta, impl=local_impl)


def ens_a2a(Z, lam, eta, mesh: Mesh, zspecs, wspecs, client_axes,
            local_impl: str = "ref"):
    """Coordinate-sharded ENS via shard_map all_to_all (beyond-paper).

    Per leaf (m, ...): each client group holds its own z_i; all_to_all
    swaps the client axis for a coordinate slice, local ENS reduces m -> 1,
    all_gather rebuilds the aggregate. Per-device traffic drops from
    O(m*n_loc) (gather) to O(2*n_loc).
    """
    axis = client_axes if len(client_axes) > 1 else client_axes[0]
    flat_axes = tuple(client_axes)
    groups = int(np.prod([mesh.shape[a] for a in flat_axes]))

    def per_leaf(z, zspec, wspec):
        def local(zl):
            # zl: (m_loc, ...) local block; m_loc = m // groups
            m_loc = zl.shape[0]
            F = int(np.prod(zl.shape[1:]))
            flat = zl.reshape(m_loc, F)
            pad = (-F) % groups
            if pad:
                flat = jnp.pad(flat, ((0, 0), (0, pad)))
            Fp = flat.shape[1]
            # one hop per client mesh axis: split coords, concat clients
            for ax in flat_axes:
                flat = lax.all_to_all(
                    flat.reshape(m_loc, -1), ax, split_axis=1,
                    concat_axis=0, tiled=True)
                m_loc = flat.shape[0]
            # flat: (m, Fp/groups) -- all clients, our coordinate slice
            w_loc = ens_ops.ens(flat, lam, eta, impl=local_impl)  # (Fp/g,)
            for ax in reversed(flat_axes):
                w_loc = lax.all_gather(w_loc, ax, axis=0, tiled=True)
            w = w_loc[:F] if pad else w_loc
            return w.reshape(zl.shape[1:])  # local feature block shape

        return shard_map(
            local, mesh=mesh,
            in_specs=(zspec,), out_specs=wspec,
            check_vma=False)(z)

    return jax.tree_util.tree_map(per_leaf, Z, zspecs, wspecs)


# ---------------------------------------------------------------------------
# rounds
# ---------------------------------------------------------------------------

def _loss_and_grad(loss_fn, remat: bool):
    f = jax.remat(loss_fn) if remat else loss_fn
    return jax.grad(f)


def spatial_round(state: FedEPMState, batches, loss_fn, cfg: FedEPMConfig,
                  mesh: Mesh, dist: DistConfig, sspecs: FedEPMState,
                  arch_cfg):
    """One communication round, clients = device groups (vmap over m)."""
    key, k_sel, k_noise = jax.random.split(state.key, 3)
    round_idx = state.k // cfg.k0
    mask = _select(k_sel, cfg, round_idx)

    # ---- server: ENS aggregation (19) ----
    if dist.ens == "a2a":
        w_new = ens_a2a(state.Z, cfg.lam, cfg.eta, mesh, sspecs.Z,
                        sspecs.w_tau, dist.client_axes,
                        local_impl=cfg.ens_impl if cfg.ens_impl != "oracle"
                        else "ref")
        w_new = tmap(lambda x, z: x.astype(z.dtype), w_new, state.Z)
    else:
        w_new = ens_gather(state.Z, cfg.lam, cfg.eta,
                           local_impl="ref")
    w_new = sh.constrain_tree(w_new, sspecs.w_tau, mesh)
    w_comp = tmap(lambda x: x.astype(arch_cfg.dtype)
                  if x.dtype == jnp.bfloat16 else x, w_new)

    # ---- clients: one gradient per round at w^{tau+1} (18), in parallel --
    # spmd_axis_name pins the vmapped client axis to the client mesh axes,
    # so every per-client intermediate (activations, grads) stays sharded
    # over ("pod","data") instead of silently replicating.
    san = dist.client_axes if len(dist.client_axes) > 1 \
        else dist.client_axes[0]
    grad_fn = _loss_and_grad(loss_fn, dist.remat)
    g = jax.vmap(lambda b: grad_fn(w_comp, b), spmd_axis_name=san)(batches)
    g = sh.constrain_tree(g, sspecs.W, mesh)

    # ---- k0 inner prox iterations (20), vmapped over clients ----
    W_upd, mu_last = jax.vmap(
        lambda wi, gi: _client_inner(wi, w_new, gi, state.k, cfg),
        spmd_axis_name=san,
    )(state.W, g)
    sdt = dist.state_dtype
    if sdt is not None:
        W_upd = tmap(lambda x: x.astype(sdt), W_upd)
    W_upd = sh.constrain_tree(W_upd, sspecs.W, mesh)
    W_next = tree_where_client(mask, W_upd, state.W)

    # ---- DP-noised upload (21)/(39) ----
    grad_l1 = jax.vmap(lambda gi: dp.sensitivity_surrogate(gi) / 2.0)(g)
    delta_hat = 2.0 * grad_l1
    if cfg.sensitivity_clip > 0:
        delta_hat = jnp.minimum(delta_hat, cfg.sensitivity_clip)
    if cfg.eps_dp > 0:
        scale = dp.fedepm_noise_scale(delta_hat, cfg.eps_dp, mu_last)
        keys = jax.random.split(k_noise, cfg.m)
        noise = jax.vmap(lambda kk, wi, s: dp.laplace_tree(kk, wi, s),
                         spmd_axis_name=san)(keys, W_upd, scale)
        Z_upd = tmap(jnp.add, W_upd, noise)
        snr_i = jax.vmap(dp.snr_db10)(W_upd, noise)
        snr = jnp.min(jnp.where(mask, snr_i, jnp.inf))
    else:
        scale = jnp.zeros((cfg.m,))
        Z_upd = W_upd
        snr = jnp.asarray(jnp.inf)
    Z_upd = sh.constrain_tree(Z_upd, sspecs.Z, mesh)
    Z_next = tree_where_client(mask, Z_upd, state.Z)

    drift = tree_sq_norm(tmap(lambda a, b: a - b, w_new, state.w_tau))
    new_state = FedEPMState(
        w_tau=w_new, W=W_next, Z=Z_next,
        k=state.k + jnp.asarray(cfg.k0, jnp.int32), key=key)
    metrics = RoundMetrics(mu_last=mu_last, grad_l1=grad_l1, snr=snr,
                           drift=drift, selected=mask, noise_scale=scale)
    return new_state, metrics


def temporal_round(state: FedEPMState, batches, loss_fn, cfg: FedEPMConfig,
                   mesh: Mesh, dist: DistConfig, sspecs: FedEPMState,
                   arch_cfg):
    """One communication round, clients time-multiplexed (scan over m).

    Identical math to spatial_round; the m axis is local, so ENS is pure
    per-device compute and peak memory holds ONE client's activations.
    """
    key, k_sel, k_noise = jax.random.split(state.key, 3)
    round_idx = state.k // cfg.k0
    mask = _select(k_sel, cfg, round_idx)

    # ---- server: ENS is local (m unsharded on every device) ----
    w_new = ens_gather(state.Z, cfg.lam, cfg.eta, local_impl="ref")
    w_new = sh.constrain_tree(w_new, sspecs.w_tau, mesh)
    w_comp = tmap(lambda x: x.astype(arch_cfg.dtype)
                  if x.dtype == jnp.bfloat16 else x, w_new)

    grad_fn = _loss_and_grad(loss_fn, dist.remat)
    keys = jax.random.split(k_noise, cfg.m)
    sdt = dist.state_dtype

    def per_client(carry, xs):
        wi, zi, bi, mi, kk, kidx = xs
        # one gradient per round at the broadcast point (18); optionally
        # accumulated over microbatches (fp32 accumulator) so one client's
        # activation footprint is 1/microbatch of its shard
        if dist.microbatch > 1:
            nmb = dist.microbatch

            def split(x):
                return x.reshape((nmb, x.shape[0] // nmb) + x.shape[1:])

            def mb_step(acc, bmb):
                gmb = grad_fn(w_comp, bmb)
                gmb = sh.constrain_tree(gmb, sspecs.w_tau, mesh)
                return tmap(lambda a, g: a + g.astype(jnp.float32),
                            acc, gmb), None

            acc0 = tmap(lambda x: jnp.zeros(x.shape, jnp.float32), w_comp)
            acc0 = sh.constrain_tree(acc0, sspecs.w_tau, mesh)
            gacc, _ = lax.scan(mb_step, acc0, tmap(split, bi))
            gi = tmap(lambda x: (x / nmb), gacc)
        else:
            gi = grad_fn(w_comp, bi)
        gi = sh.constrain_tree(gi, sspecs.w_tau, mesh)
        wi_upd, mu_last = _client_inner(wi, w_new, gi, state.k, cfg)
        if sdt is not None:
            wi_upd = tmap(lambda x: x.astype(sdt), wi_upd)
        grad_l1 = dp.sensitivity_surrogate(gi) / 2.0
        delta_hat = 2.0 * grad_l1
        if cfg.sensitivity_clip > 0:
            delta_hat = jnp.minimum(delta_hat, cfg.sensitivity_clip)
        if cfg.eps_dp > 0:
            scale = dp.fedepm_noise_scale(delta_hat, cfg.eps_dp, mu_last)
            noise = dp.laplace_tree(kk, wi_upd, scale)
            zi_upd = tmap(jnp.add, wi_upd, noise)
            snr_i = dp.snr_db10(wi_upd, noise)
        else:
            scale = jnp.asarray(0.0)
            zi_upd = wi_upd
            snr_i = jnp.asarray(jnp.inf)
        # eq. (22): carry state through for non-selected clients
        wi_next = tmap(lambda a, b: jnp.where(mi, a, b), wi_upd, wi)
        zi_next = tmap(lambda a, b: jnp.where(mi, a, b), zi_upd, zi)
        return carry, (wi_next, zi_next,
                       (mu_last, grad_l1, jnp.where(mi, snr_i, jnp.inf),
                        scale))

    _, (W_next, Z_next, (mu_last, grad_l1, snr_i, scale)) = lax.scan(
        per_client, None,
        (state.W, state.Z, batches, mask, keys,
         jnp.arange(cfg.m, dtype=jnp.int32)))
    W_next = sh.constrain_tree(W_next, sspecs.W, mesh)
    Z_next = sh.constrain_tree(Z_next, sspecs.Z, mesh)

    snr = jnp.min(snr_i)
    drift = tree_sq_norm(tmap(lambda a, b: a - b, w_new, state.w_tau))
    new_state = FedEPMState(
        w_tau=w_new, W=W_next, Z=Z_next,
        k=state.k + jnp.asarray(cfg.k0, jnp.int32), key=key)
    metrics = RoundMetrics(mu_last=mu_last, grad_l1=grad_l1, snr=snr,
                           drift=drift, selected=mask, noise_scale=scale)
    return new_state, metrics


# ---------------------------------------------------------------------------
# builder
# ---------------------------------------------------------------------------

def build_fedepm(model, loss_fn, fed_cfg: FedEPMConfig, mesh: Mesh,
                 dist: DistConfig):
    """Returns (init_fn, step_fn, sspecs_fn).

    init_fn(key)            -> FedEPMState (all clients at the same w0)
    step_fn(state, batches) -> (state, metrics)   [to be jit'd by caller
                               with in/out shardings from sspecs_fn]
    sspecs_fn(abstract_state) -> FedEPMState of PartitionSpecs
    """
    arch_cfg = model.cfg

    def init_fn(key):
        k1, k2 = jax.random.split(key)
        params0 = model.init(k1)
        sdt = dist.state_dtype
        if sdt is not None:
            params_state = tmap(lambda x: x.astype(sdt), params0)
        else:
            params_state = params0
        W = tmap(lambda x: jnp.broadcast_to(x[None],
                                            (fed_cfg.m,) + x.shape),
                 params_state)
        # w_tau lives in the same dtype as the uploads (ENS output dtype),
        # so the state signature is round-invariant (donation-safe)
        return FedEPMState(w_tau=params_state, W=W, Z=W,
                           k=jnp.asarray(0, jnp.int32), key=k2)

    def sspecs_fn(abstract_state):
        return state_specs(arch_cfg, abstract_state, mesh, dist)

    round_fn = spatial_round if dist.mode == "spatial" else temporal_round

    def step_fn(state, batches, sspecs):
        return round_fn(state, batches, loss_fn, fed_cfg, mesh, dist,
                        sspecs, arch_cfg)

    return init_fn, step_fn, sspecs_fn
