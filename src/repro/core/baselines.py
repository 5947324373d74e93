"""Benchmark algorithms from the paper: SFedAvg and SFedProx (Algorithm 3).

Both share the Algorithm-3 skeleton: mean aggregation over the *selected*
clients' noisy uploads (34), periodic communication at k in K, Laplace-noised
uploads. They differ in the client update:

  SFedAvg  (35): one full-gradient step per iteration,
                 at the broadcast point when k in K, else locally.
  SFedProx (36)+Alg.4: ell inexact GD steps on
                 f_i(w) + (mu/2)||w - w^{tau}||^2 per iteration.

Step size (38): gamma_i^k = 2 d_i / sqrt(2 k0 + floor(k/k0)); d_i is client
i's sample count (the 1/d_i inside f_i makes this scale sensible).

Noise for baselines: the paper states noise is added on upload but does not
print the baselines' scale. We use the same sensitivity surrogate with a
harmonically-decaying denominator, b_i = 2 * (2||g_i||_1) / (eps_dp * (tau+1))
-- decaying like 1/tau (vs FedEPM's geometric alpha^k via mu), which is the
usual choice for DP-SGD-style baselines and reproduces the paper's relative
SNR ordering. Documented in EXPERIMENTS.md.
"""
from __future__ import annotations

import dataclasses
from typing import NamedTuple

import jax
import jax.numpy as jnp

from repro.core import dp
from repro.core.fedepm import Batch, LossFn, Params
from repro.core.participation import sample_uniform
from repro.core.treeutil import (
    tmap,
    tree_broadcast_clients,
    tree_where,
    tree_where_client,
)


@dataclasses.dataclass(frozen=True)
class BaselineConfig:
    m: int
    k0: int = 4
    rho: float = 0.5
    eps_dp: float = 0.1
    d_i: float = 1.0          # per-client sample count (for gamma, eq. (38))
    prox_mu: float = 1e-5     # SFedProx inner mu
    prox_ell: int = 3         # SFedProx inner GD steps (Alg. 4)
    gamma_scale: float = 2.0  # the "2 d_i" prefactor knob


class BaselineState(NamedTuple):
    w_tau: Params
    W: Params     # stacked (m, ...)
    Z: Params
    k: jax.Array
    key: jax.Array


class BaselineMetrics(NamedTuple):
    snr: jax.Array
    selected: jax.Array
    grad_l1: jax.Array


def init_state(key: jax.Array, params0: Params, cfg: BaselineConfig) -> BaselineState:
    W = tree_broadcast_clients(params0, cfg.m)
    return BaselineState(w_tau=params0, W=W, Z=W,
                         k=jnp.asarray(0, jnp.int32), key=key)


def default_round_mask(state: BaselineState, cfg: BaselineConfig) -> jax.Array:
    """The mask sfedavg_round/sfedprox_round would draw for ``state``.

    Mirrors the rounds' key split so the systems runtime (repro.sim) can
    supply arrival-aware masks that degrade gracefully to the internal
    selection (same key stream => bit-identical trajectories)."""
    _, k_sel, _ = jax.random.split(state.key, 3)
    return sample_uniform(k_sel, cfg.m, cfg.rho)


def _gamma(cfg: BaselineConfig, k):
    """Eq. (38): gamma = gamma_scale * d_i / sqrt(2 k0 + tau_k)."""
    tau = (k // cfg.k0).astype(jnp.float32)
    return cfg.gamma_scale * cfg.d_i / jnp.sqrt(2.0 * cfg.k0 + tau)


@jax.named_scope("aggregate")
def _aggregate_selected_mean(Z, mask):
    """Eq. (34): mean over selected uploads."""
    cnt = jnp.maximum(jnp.sum(mask), 1).astype(jnp.float32)

    def agg(z):
        mm = mask.reshape((-1,) + (1,) * (z.ndim - 1))
        return jnp.sum(jnp.where(mm, z, 0.0), axis=0) / cnt

    return tmap(agg, Z)


@jax.named_scope("dp_noise")
def _noisy_upload(k_noise, W_upd, g, mask, cfg: BaselineConfig, k):
    grad_l1 = jax.vmap(lambda gi: dp.sensitivity_surrogate(gi) / 2.0)(g)
    if cfg.eps_dp <= 0:
        return W_upd, jnp.asarray(jnp.inf), grad_l1
    tau = (k // cfg.k0).astype(jnp.float32)
    scale = 2.0 * (2.0 * grad_l1) / (cfg.eps_dp * (tau + 1.0))
    keys = jax.random.split(k_noise, cfg.m)
    noise = jax.vmap(lambda kk, wi, s: dp.laplace_tree(kk, wi, s))(
        keys, W_upd, scale)
    Z_upd = tmap(jnp.add, W_upd, noise)
    snr_i = jax.vmap(dp.snr_db10)(W_upd, noise)
    snr = jnp.min(jnp.where(mask, snr_i, jnp.inf))
    return Z_upd, snr, grad_l1


def sfedavg_round(state: BaselineState, batches: Batch, loss_fn: LossFn,
                  cfg: BaselineConfig, mask: jax.Array | None = None,
                  agg_mask: jax.Array | None = None):
    """k0 iterations of SFedAvg (Algorithm 3 + eq. (35)).

    ``mask`` optionally supplies the participation set externally (see
    fedepm.fedepm_round); the key split is unchanged either way.
    ``agg_mask`` optionally decouples eq. (34)'s aggregation support from
    the participation set: the broadcast point averages the Z rows of
    ``agg_mask`` (default: ``mask``, the paper's selected-mean) while only
    ``mask`` clients compute and upload. The async client-level scheduler
    (repro.sim) uses this to anchor a sub-cohort dispatch group's broadcast
    on its whole cohort, mirroring how FedEPM's ENS aggregates every
    client's latest upload."""
    key, k_sel, k_noise = jax.random.split(state.key, 3)
    if mask is None:
        mask = sample_uniform(k_sel, cfg.m, cfg.rho)
    w_new = _aggregate_selected_mean(
        state.Z, mask if agg_mask is None else agg_mask)
    grad_fn = jax.grad(loss_fn)

    def client(wi, b):
        # t = 0 is the communication step: start from the broadcast point.
        def step(w, t):
            k = state.k + t
            gamma = _gamma(cfg, k)
            base = tree_where(t == 0, w_new, w)
            gi = grad_fn(base, b)
            w = tmap(lambda a, g_: a - gamma * g_, base, gi)
            return w, None

        w_final, _ = jax.lax.scan(step, wi, jnp.arange(cfg.k0, dtype=jnp.int32))
        g_last = grad_fn(w_final, b)
        return w_final, g_last

    with jax.named_scope("client_update"):
        W_upd, g = jax.vmap(client)(state.W, batches)
    W_next = tree_where_client(mask, W_upd, state.W)
    Z_upd, snr, grad_l1 = _noisy_upload(k_noise, W_upd, g, mask, cfg, state.k)
    Z_next = tree_where_client(mask, Z_upd, state.Z)
    new_state = BaselineState(w_tau=w_new, W=W_next, Z=Z_next,
                              k=state.k + jnp.asarray(cfg.k0, jnp.int32),
                              key=key)
    return new_state, BaselineMetrics(snr=snr, selected=mask, grad_l1=grad_l1)


def sfedprox_round(state: BaselineState, batches: Batch, loss_fn: LossFn,
                   cfg: BaselineConfig, mask: jax.Array | None = None,
                   agg_mask: jax.Array | None = None):
    """k0 iterations of SFedProx (Algorithm 3 + (36), inner solver Alg. 4).

    ``mask`` optionally supplies the participation set externally (see
    fedepm.fedepm_round); the key split is unchanged either way.
    ``agg_mask`` decouples eq. (34)'s aggregation support from the
    participation set exactly as in ``sfedavg_round``."""
    key, k_sel, k_noise = jax.random.split(state.key, 3)
    if mask is None:
        mask = sample_uniform(k_sel, cfg.m, cfg.rho)
    w_new = _aggregate_selected_mean(
        state.Z, mask if agg_mask is None else agg_mask)
    grad_fn = jax.grad(loss_fn)

    def client(wi, b):
        def outer(w, t):
            k = state.k + t
            gamma = _gamma(cfg, k)
            # Alg. 4: v^1 = w^{tau} if k in K (t==0) else w_i^k
            v = tree_where(t == 0, w_new, w)

            def inner(vt, _):
                gi = grad_fn(vt, b)
                vt = tmap(
                    lambda vv, g_, wt: vv - gamma * (g_ + cfg.prox_mu * (vv - wt)),
                    vt, gi, w_new)
                return vt, None

            v, _ = jax.lax.scan(inner, v, jnp.arange(cfg.prox_ell))
            return v, None

        w_final, _ = jax.lax.scan(outer, wi, jnp.arange(cfg.k0, dtype=jnp.int32))
        g_last = grad_fn(w_final, b)
        return w_final, g_last

    with jax.named_scope("client_update"):
        W_upd, g = jax.vmap(client)(state.W, batches)
    W_next = tree_where_client(mask, W_upd, state.W)
    Z_upd, snr, grad_l1 = _noisy_upload(k_noise, W_upd, g, mask, cfg, state.k)
    Z_next = tree_where_client(mask, Z_upd, state.Z)
    new_state = BaselineState(w_tau=w_new, W=W_next, Z=Z_next,
                              k=state.k + jnp.asarray(cfg.k0, jnp.int32),
                              key=key)
    return new_state, BaselineMetrics(snr=snr, selected=mask, grad_l1=grad_l1)


def scan_round(state: BaselineState, xs, batches: Batch, loss_fn: LossFn,
               cfg: BaselineConfig, round_fn):
    """Scan-compatible round body: ``(carry=state, x=(mask, abandoned))``.

    ``round_fn`` is ``sfedavg_round`` or ``sfedprox_round``. Semantics
    match ``core.fedepm.scan_round``: an abandoned round carries the state
    (and key) through untouched; metrics are emitted shape-stably and must
    be ignored for abandoned rounds. The fused engine (repro.sim.engine)
    scans this body directly in its codec-free path.
    """
    mask, abandoned = xs
    new_state, metrics = round_fn(state, batches, loss_fn, cfg, mask=mask)
    return tree_where(abandoned, state, new_state), metrics


def make_scan_rounds(batches, loss_fn, cfg, round_fn, *, donate: bool = True):
    """Compile K baseline rounds into ONE on-device ``jax.lax.scan``.

    ``round_fn`` is ``sfedavg_round`` or ``sfedprox_round``. Semantics match
    ``core.fedepm.make_scan_rounds``: ``run(state, masks, abandoned)`` scans
    a precomputed (K, m) participation-mask stream, abandoned rounds carry
    the state (and key) through untouched, per-round metrics stack
    on-device, and with ``donate`` the input state's buffers are reused for
    the output instead of copied.
    """
    def run(state, masks, abandoned):
        return jax.lax.scan(
            lambda c, x: scan_round(c, x, batches, loss_fn, cfg, round_fn),
            state, (masks, abandoned))

    return jax.jit(run, donate_argnums=(0,) if donate else ())
