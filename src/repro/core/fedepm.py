"""FedEPM -- the paper's Algorithm 2, as a composable JAX module.

The round function is pure and jit-safe; it operates on *stacked* client
parameter pytrees (leading axis m), so it can run

  * single-host (vmap over clients) for the paper-scale reproduction, or
  * multi-pod, with the client axis sharded over mesh axes ("pod","data")
    and feature axes over "model" (see repro/launch and core/distributed).

Faithfulness notes
------------------
* Iteration layout follows Algorithm 2 exactly: communication happens at
  k in K = {0, k0, 2k0, ...}. One call to ``round`` advances k0 iterations:
  aggregate current uploads Z via ENS (19), broadcast w^{tau+1}, compute the
  round gradient g_i = grad f_i(w^{tau+1}) once (18), run k0 inner
  closed-form prox iterations (20) with growing mu_{i,k+1}, then DP-noise and
  upload z_i (21). Non-selected clients carry state through, eq. (22).
* mu_{i,k+1} = mu_{i,0} (1 + c_i ||w_i^k - w^{tau+1}||^2) alpha_i^{k+1} is
  recomputed from the *current* iterate at every inner step, as in (20).
* The initial uploads z_i^0 = w_i^0 + eps_i^0: since w_i^0 is data-independent
  (a public constant or PRNG init), no DP noise is required at k=0; we expose
  ``init_noise_scale`` (default 0) to match the paper's optional eps_i^0.
"""
from __future__ import annotations

import dataclasses
from typing import Any, Callable, NamedTuple

import jax
import jax.numpy as jnp

from repro.core import dp
from repro.core.participation import sample_coverage, sample_uniform
from repro.core.treeutil import (
    tmap,
    tree_broadcast_clients,
    tree_sq_norm,
    tree_where,
    tree_where_client,
)
from repro.kernels.ens import ops as ens_ops
from repro.kernels.prox import ops as prox_ops

Params = Any
Batch = Any
LossFn = Callable[[Params, Batch], jax.Array]


@dataclasses.dataclass(frozen=True)
class FedEPMConfig:
    m: int                       # number of clients
    k0: int = 4                  # iterations between communications
    lam: float = 1e-5            # elastic-net l1 weight  (lambda)
    eta: float = 2e-5            # elastic-net l2 weight  (eta); paper: lam = eta/2
    mu0: float = 0.05            # mu_{i,0}
    c: float = 1e-8              # c_i
    alpha: float = 1.001         # alpha_i > 1
    rho: float = 0.5             # participation fraction
    eps_dp: float = 0.1          # DP epsilon; <= 0 disables noise
    s0: int = 10                 # coverage window (Setup VI.1)
    sampler: str = "uniform"     # "uniform" | "coverage" | "full"
    ens_impl: str = "ref"        # "ref" | "pallas" | "oracle"
    prox_impl: str = "ref"       # "ref" | "pallas"
    init_noise_scale: float = 0.0
    # beyond-paper hardening: cap the sensitivity surrogate Delta_hat =
    # 2||g||_1 (eq. (39) is calibrated for n=14; at LM scale ||g||_1
    # grows with the parameter count and the un-capped noise overflows
    # fp32 -> NaN). 0 disables. With clipping, eps-DP holds for the
    # CLIPPED mechanism (dp.clip_tree_l1 enforces the bound).
    sensitivity_clip: float = 0.0

    @staticmethod
    def paper_defaults(m: int, rho: float = 0.5, k0: int = 12,
                       eps_dp: float = 0.1, **kw) -> "FedEPMConfig":
        """The paper's Sec. VII.B settings: eta=(0.02m+1)(rho+0.1)1e-5, lam=eta/2."""
        eta = (0.02 * m + 1.0) * (rho + 0.1) * 1e-5
        return FedEPMConfig(m=m, k0=k0, lam=eta / 2.0, eta=eta, rho=rho,
                            eps_dp=eps_dp, **kw)


class FedEPMState(NamedTuple):
    w_tau: Params    # last broadcast point w^{tau_k}
    W: Params        # stacked client iterates, leading axis m
    Z: Params        # stacked (noisy) uploads, leading axis m
    k: jax.Array     # global iteration counter (int32, multiple of k0)
    key: jax.Array


class RoundMetrics(NamedTuple):
    mu_last: jax.Array       # (m,) final mu_{i,k+1} of the round
    grad_l1: jax.Array       # (m,) ||g_i||_1
    snr: jax.Array           # paper SNR: min_i log10(||w_i||/||eps_i||)
    drift: jax.Array         # ||w^{tau+1} - w^{tau}||^2
    selected: jax.Array      # (m,) participation mask
    noise_scale: jax.Array   # (m,) Laplace scale b_i used this round


def init_state(key: jax.Array, params0: Params, cfg: FedEPMConfig) -> FedEPMState:
    """All clients start from the same w_i^0 = params0 (paper: w_i^0 = 0)."""
    W = tree_broadcast_clients(params0, cfg.m)
    if cfg.init_noise_scale > 0:
        key, sub = jax.random.split(key)
        noise = dp.laplace_tree(sub, W, cfg.init_noise_scale)
        Z = tmap(jnp.add, W, noise)
    else:
        Z = W
    return FedEPMState(w_tau=params0, W=W, Z=Z,
                       k=jnp.asarray(0, jnp.int32), key=key)


def _select(key, cfg: FedEPMConfig, round_idx):
    if cfg.sampler == "uniform":
        return sample_uniform(key, cfg.m, cfg.rho)
    if cfg.sampler == "coverage":
        return sample_coverage(key, cfg.m, cfg.rho, round_idx, cfg.s0)
    if cfg.sampler == "full":
        return jnp.ones((cfg.m,), bool)
    raise ValueError(f"unknown sampler {cfg.sampler!r}")


def default_round_mask(state: FedEPMState, cfg: FedEPMConfig) -> jax.Array:
    """The mask ``fedepm_round`` would draw for ``state`` this round.

    Replicates the round's key split so an external scheduler (repro.sim)
    can reproduce the internal selection exactly: supplying this mask via
    ``fedepm_round(..., mask=...)`` yields bit-identical trajectories.
    """
    _, k_sel, _ = jax.random.split(state.key, 3)
    return _select(k_sel, cfg, state.k // cfg.k0)


def _client_inner(wi, w_new, gi, k_start, cfg: FedEPMConfig):
    """k0 closed-form prox iterations (20) for ONE client. Returns (wi, mu_last)."""

    def step(carry, t):
        w = carry
        k = k_start + t  # current global iteration index k
        mu = cfg.mu0 * (1.0 + cfg.c * tree_sq_norm(tmap(jnp.subtract, w, w_new))) \
            * jnp.power(cfg.alpha, (k + 1).astype(jnp.float32))
        w = prox_ops.prox_update_tree(w, w_new, gi, mu, cfg.lam, cfg.eta,
                                      impl=cfg.prox_impl)
        return w, mu

    wi_final, mus = jax.lax.scan(step, wi, jnp.arange(cfg.k0, dtype=jnp.int32))
    return wi_final, mus[-1]


def fedepm_round(state: FedEPMState, batches: Batch, loss_fn: LossFn,
                 cfg: FedEPMConfig, mask: jax.Array | None = None):
    """One communication round = k0 iterations of Algorithm 2.

    ``batches`` is a pytree with a leading client axis m (each client's local
    data or minibatch). Returns (new_state, RoundMetrics).

    ``mask`` optionally supplies the participation set externally (shape (m,)
    bool) -- used by the systems runtime (repro.sim) where selection is a
    function of simulated arrival times. The key split is unchanged whether
    or not a mask is given, so passing ``default_round_mask(state, cfg)``
    reproduces the internal selection bit-for-bit. Non-selected clients
    carry state through either way, eq. (22).
    """
    key, k_sel, k_noise = jax.random.split(state.key, 3)
    round_idx = state.k // cfg.k0
    if mask is None:
        mask = _select(k_sel, cfg, round_idx)

    # ---- server: aggregate uploads via ENS (19) and broadcast ----
    with jax.named_scope("ens"):
        w_new = ens_ops.ens_tree(state.Z, cfg.lam, cfg.eta,
                                 impl=cfg.ens_impl)

    # ---- clients: one gradient per round at the broadcast point (18) ----
    with jax.named_scope("client_grad"):
        grad_fn = jax.grad(loss_fn)
        g = jax.vmap(lambda b: grad_fn(w_new, b))(batches)  # stacked (m, ...)

    # ---- k0 inner prox iterations per client (20) ----
    with jax.named_scope("client_prox"):
        W_upd, mu_last = jax.vmap(
            lambda wi, gi: _client_inner(wi, w_new, gi, state.k, cfg)
        )(state.W, g)
    W_next = tree_where_client(mask, W_upd, state.W)

    # ---- DP-noised upload (21)/(39) ----
    with jax.named_scope("dp_noise"):
        grad_l1 = jax.vmap(lambda gi: dp.sensitivity_surrogate(gi) / 2.0)(g)
        delta_hat = 2.0 * grad_l1
        if cfg.sensitivity_clip > 0:
            delta_hat = jnp.minimum(delta_hat, cfg.sensitivity_clip)
        if cfg.eps_dp > 0:
            scale = dp.fedepm_noise_scale(delta_hat, cfg.eps_dp,
                                          mu_last)  # (m,)
            keys = jax.random.split(k_noise, cfg.m)
            noise = jax.vmap(lambda kk, wi, s: dp.laplace_tree(kk, wi, s))(
                keys, W_upd, scale)
            Z_upd = tmap(jnp.add, W_upd, noise)
            snr_i = jax.vmap(dp.snr_db10)(W_upd, noise)  # (m,)
            snr = jnp.min(jnp.where(mask, snr_i, jnp.inf))
        else:
            scale = jnp.zeros((cfg.m,))
            Z_upd = W_upd
            snr = jnp.asarray(jnp.inf)
    Z_next = tree_where_client(mask, Z_upd, state.Z)

    drift = tree_sq_norm(tmap(jnp.subtract, w_new, state.w_tau))
    new_state = FedEPMState(
        w_tau=w_new, W=W_next, Z=Z_next,
        k=state.k + jnp.asarray(cfg.k0, jnp.int32), key=key)
    metrics = RoundMetrics(mu_last=mu_last, grad_l1=grad_l1, snr=snr,
                           drift=drift, selected=mask, noise_scale=scale)
    return new_state, metrics


def scan_round(state: FedEPMState, xs, batches: Batch, loss_fn: LossFn,
               cfg: FedEPMConfig):
    """Scan-compatible round body: ``(carry=state, x=(mask, abandoned))``.

    One step of ``jax.lax.scan`` over a precomputed participation-mask
    stream (repro.sim.engine). ``abandoned`` is a scalar bool: an abandoned
    round (every contacted client offline) leaves the carried state --
    including the PRNG key -- untouched, exactly like the eager simulation
    path that never calls the round function. Metrics are still emitted
    (shape-stable for stacking) and must be ignored by the caller for
    abandoned rounds.
    """
    mask, abandoned = xs
    new_state, metrics = fedepm_round(state, batches, loss_fn, cfg,
                                      mask=mask)
    return tree_where(abandoned, state, new_state), metrics


def make_scan_rounds(batches: Batch, loss_fn: LossFn, cfg: FedEPMConfig,
                     *, donate: bool = True):
    """Compile K rounds into ONE on-device ``jax.lax.scan``.

    Returns ``run(state, masks, abandoned) -> (state, stacked RoundMetrics)``
    with ``masks`` (K, m) bool and ``abandoned`` (K,) bool. With ``donate``
    the input state's buffers are donated to the XLA call and reused for the
    output state instead of being copied -- the caller must not touch the
    passed-in state afterwards. Per-round metrics are stacked on-device and
    transferred once, not round by round.
    """
    def run(state, masks, abandoned):
        return jax.lax.scan(
            lambda c, x: scan_round(c, x, batches, loss_fn, cfg),
            state, (masks, abandoned))

    return jax.jit(run, donate_argnums=(0,) if donate else ())


def global_objective(loss_fn: LossFn, w: Params, batches: Batch) -> jax.Array:
    """f(w) = sum_i f_i(w) over the stacked client batches (paper eq. (1))."""
    return jnp.sum(jax.vmap(lambda b: loss_fn(w, b))(batches))


def global_grad_sq_norm(loss_fn: LossFn, w: Params, batches: Batch) -> jax.Array:
    """||grad f(w)||^2 for the paper's termination rule."""
    g = jax.grad(lambda p: global_objective(loss_fn, p, batches))(w)
    return tree_sq_norm(g)


def lyapunov(loss_fn: LossFn, state: FedEPMState, batches: Batch,
             cfg: FedEPMConfig) -> jax.Array:
    """The descent quantity F(w^{tau_k}, W^k) of (7) (noise-free part of L^k).

    Used by tests/benchmarks to check Lemma VI.1's monotone-descent claim.
    """
    fvals = jax.vmap(lambda wi, b: loss_fn(wi, b))(state.W, batches)
    pen = jax.vmap(
        lambda wi: cfg.lam * sum(
            jnp.sum(jnp.abs(a - b)) for a, b in zip(
                jax.tree_util.tree_leaves(wi),
                jax.tree_util.tree_leaves(state.w_tau))
        ) + 0.5 * cfg.eta * tree_sq_norm(
            tmap(jnp.subtract, wi, state.w_tau))
    )(state.W)
    return jnp.sum(fvals + pen)
