"""Plain reference of the paper's task: l2-regularised logistic regression
on a synthetic stand-in for UCI Adult (paper Sec. VII.A).

Data: d rows of n features, n - 8 standard normal and 8 small integer codes
of random cardinality 2..15, every column scaled to unit Euclidean norm;
labels drawn from a logistic model on the scaled features (logits
standardised to scale 2.5), 5% flipped. The rows are dealt to the m clients
by one random permutation, in m contiguous parts of near-equal size.

Per-client loss: mean over the client's rows of softplus(x.w) - y (x.w),
plus (beta / 2) ||w||^2 with beta = 1e-3. The configuration computes in
float32; the control computes in bfloat16.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

# the per-round objective is read every round (the parameters are a flat
# vector, so every round's broadcast point reaches the host)
OBJECTIVE_EVERY_ROUND = True
BETA = 1e-3


def make_data(cfg: dict, seed: int):
    """-> (client batches, initial parameters, mean rows per client)."""
    task = cfg["spec"]["task"]
    d, n, m = task["d"], task["n"], task["m"]
    rng = np.random.default_rng(seed)
    n_cat = 8
    X_cont = rng.standard_normal((d, n - n_cat))
    cards = rng.integers(2, 16, size=n_cat)
    X_cat = np.stack([rng.integers(0, c, size=d) for c in cards], axis=1)
    X = np.concatenate([X_cont, X_cat.astype(np.float64)], axis=1)
    Xn = X / (np.linalg.norm(X, axis=0, keepdims=True) + 1e-12)
    w_true = rng.standard_normal(n)
    w_true /= np.linalg.norm(w_true)
    raw = Xn @ w_true
    logits = 2.5 * (raw - raw.mean()) / (raw.std() + 1e-12)
    p = 1.0 / (1.0 + np.exp(-logits))
    y = (rng.random(d) < p).astype(np.float32)
    flip = rng.random(d) < 0.05
    y[flip] = 1.0 - y[flip]
    X = Xn.astype(np.float32)

    parts = np.array_split(np.random.default_rng(seed).permutation(d), m)
    rows = max(len(s) for s in parts)
    x = np.zeros((m, rows, n), np.float32)
    yy = np.zeros((m, rows), np.float32)
    mask = np.zeros((m, rows), np.float32)
    for i, s in enumerate(parts):
        x[i, :len(s)], yy[i, :len(s)], mask[i, :len(s)] = X[s], y[s], 1.0
    d_local = float(mask.reshape(m, -1).sum(axis=1).mean())
    return {"x": x, "y": yy, "mask": mask}, np.zeros(n, np.float32), d_local


def half_batch(batches: dict) -> dict:
    """Every client's second half of rows left out of its mean."""
    mask = batches["mask"].copy()
    rows = mask.shape[1]
    mask[:, rows // 2:] = 0.0
    return {**batches, "mask": mask}


def state_dtype(lower: bool):
    return jnp.bfloat16 if lower else jnp.float32


def make_loss(cfg: dict, lower: bool):
    dt = state_dtype(lower)
    prec = None if lower else jax.lax.Precision.HIGHEST

    def loss(w, b):
        x, y, mask = (b["x"].astype(dt), b["y"].astype(dt),
                      b["mask"].astype(dt))
        z = jnp.dot(x, w.astype(dt), precision=prec)
        per = jax.nn.softplus(z) - y * z
        rows = jnp.maximum(jnp.sum(mask), 1.0)
        return jnp.sum(per * mask) / rows + 0.5 * BETA * jnp.sum(w * w)

    return loss


def flops_per_round(cfg: dict) -> float:
    """Forward X.w and backward X^T r over all d rows: 4 d n."""
    task = cfg["spec"]["task"]
    return 4.0 * task["d"] * task["n"]


def tokens_per_round(cfg: dict):
    return None
