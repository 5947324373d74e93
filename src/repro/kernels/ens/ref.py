"""Pure-jnp oracles for the Elastic-Net Solver (ENS), paper Lemma III.1/III.2.

ENS solves, coordinate-wise over j in [n],

    w*_j = argmin_w  sum_{i=1..m} ( lam*|w - Z_ij| + (eta/2)*(w - Z_ij)^2 )

Four implementations are provided:

``ens_ref``     -- the production-quality jnp reference (median identity, see
                   below). O(n * m log m). This is the oracle the Pallas
                   kernel and ``ens_select`` are validated against, and the
                   jnp path for many clients (``ops._SELECT_MAX_M``).
``ens_select``  -- the same median without a sort (merge-select identity,
                   see ``ens_select``): elementwise min/max over the m client
                   rows and the m+1 candidate rows. The jnp path for small m.
``ens_oracle``  -- brute-force argmin over the full candidate set by direct
                   objective evaluation. O(n * m^2). Used only in tests as
                   an independently-correct ground truth.
``ens_paper``   -- the *literal* Algorithm 1 from the paper. NOTE: as printed,
                   Lemma III.1 has a sign error (w(s) = mean - (lam/eta)(2s/m-1)
                   should be mean + ...; equivalently the paper's s counts
                   values *below* w while its selection rule sorts
                   *descending*), and ties/edge cases (e.g. m=1) are
                   mishandled. Kept for the reproduction-notes benchmark.

The median identity
-------------------
The objective is strictly convex and piecewise quadratic. Zeroing the
subgradient on the open interval with exactly ``a`` client values strictly
above w gives the interior candidate

    c_a = mean + (lam/eta) * (2a - m)/m ,     a = 0..m,

valid when it really lies in its interval; otherwise the solution sits at a
client value (knot) where the subdifferential interval covers zero. One can
check (and tests do, against ``ens_oracle``) that the unique minimizer is the
**median of the 2m+1 values {Z_1j..Z_mj, c_0..c_m}**:

* lam -> 0: all m+1 candidates collapse onto the mean, which then holds the
  majority of the 2m+1 slots => ENS = mean (plain FedAvg aggregation).
* eta -> 0: the candidates fly off to +-inf in balanced numbers => ENS =
  median of the client values, matching the paper's eq. (5).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp


def _check_2d(Z: jax.Array) -> None:
    if Z.ndim != 2:
        raise ValueError(f"ENS expects Z of shape (m, n); got {Z.shape}")


def _mean_and_offsets(Z: jax.Array, lam, eta):
    """-> (mean (...), offs (m+1,)): the interior candidates are
    c_a = mean + offs[a], rising in a when lam/eta >= 0."""
    m = Z.shape[0]
    mean = jnp.mean(Z, axis=0)                           # (...)
    a = jnp.arange(m + 1, dtype=Z.dtype)
    offs = (lam / eta) * (2.0 * a - m) / m              # (m+1,)
    return mean, offs


def ens_candidates(Z: jax.Array, lam, eta) -> jax.Array:
    """Stack the 2m+1 per-coordinate candidates: (2m+1, ...).

    Works on ANY trailing shape -- coordinate-wise along axis 0. (No
    flattening: under pjit a (m, ...)->(m, n) reshape of a feature-sharded
    leaf is unrepresentable and silently REPLICATES the whole tensor.)
    """
    m = Z.shape[0]
    mean, offs = _mean_and_offsets(Z, lam, eta)
    offs = offs.reshape((m + 1,) + (1,) * (Z.ndim - 1))
    cands = mean[None] + offs                            # (m+1, ...)
    return jnp.concatenate([Z, cands], axis=0)


def ens_ref(Z: jax.Array, lam, eta) -> jax.Array:
    """ENS via the median identity. Z: (m, ...) -> (...)."""
    stacked = ens_candidates(Z, lam, eta)  # (2m+1, ...)
    m = Z.shape[0]
    sorted_ = jnp.sort(stacked, axis=0)
    return sorted_[m]  # middle of 2m+1


def _merge_sort_network(n: int) -> list[tuple[int, int]]:
    """Batcher's odd-even merge sort on n = 2^k rows: compare-exchange
    pairs (i, j), i < j, that leave row i <= row j."""
    pairs = []
    p = 1
    while p < n:
        k = p
        while k >= 1:
            for j in range(k % p, n - k, 2 * k):
                for i in range(min(k, n - j - k)):
                    if (i + j) // (2 * p) == (i + j + k) // (2 * p):
                        pairs.append((i + j, i + j + k))
            k //= 2
        p *= 2
    return pairs


def _sorted_rows(rows: list) -> list:
    """Sort a list of equal-shaped arrays elementwise, ascending, by a
    compare-exchange network fixed at trace time. The network is padded to
    a power of two with +inf rows; a pair that meets a pad changes nothing,
    so it emits no op."""
    m = len(rows)
    rows = list(rows)
    for i, j in _merge_sort_network(1 << max(m - 1, 0).bit_length()):
        if j < m:
            rows[i], rows[j] = (jnp.minimum(rows[i], rows[j]),
                                jnp.maximum(rows[i], rows[j]))
    return rows


def ens_select(Z: jax.Array, lam, eta) -> jax.Array:
    """ENS without a sort: the middle of the 2m+1 values by elementwise
    min/max. Z: (m, ...) -> (...); needs lam/eta >= 0. Equal to ``ens_ref``
    (the same float; only the sign of a zero may differ).

    The merge-select identity
    -------------------------
    Let Xs[0] <= .. <= Xs[m-1] be the client values, sorted by a fixed
    compare-exchange network, with Xs[m] := +inf, and C[a] = c_a the
    candidates, which rise in a since lam/eta >= 0 (fl(mean + off) is
    monotone in off). The middle of all 2m+1 values, the m-th smallest
    counting from 0, is

        w* = max_{i=0..m} min(Xs[i], C[m-i]).

    No term exceeds it: were both Xs[i] and C[m-i] above sorted_[m], the
    m+1 values at or below it would lie among Xs[:i] and C[:m-i], only m
    values. One term reaches it: with i the count of client values among
    the first m of the merged order, sorted_[m] is Xs[i] or C[m-i], and
    the other of the two lies at or after it. Every min and max returns
    one of its inputs, so the result is a value ``ens_ref`` sorts, not a
    rounding of one.

    The candidates are ``ens_candidates``' own (same ops, same order, see
    ``_mean_and_offsets``), each built as a row of its own. Past the
    mean, the whole expression is elementwise over the trailing axes: no
    row stack and no reshape of the client axis, so XLA can fuse it into
    passes that read Z and write w.
    """
    with jax.named_scope("ens_select"):
        m = Z.shape[0]
        mean, offs = _mean_and_offsets(Z, lam, eta)
        xs = _sorted_rows([Z[i] for i in range(m)])
        w = mean + offs[0]                                # i = m: Xs[m] = +inf
        for i in range(m):
            w = jnp.maximum(w, jnp.minimum(xs[i], mean + offs[m - i]))
        return w


def ens_objective(Z: jax.Array, w: jax.Array, lam, eta) -> jax.Array:
    """Per-coordinate objective sum_i lam|w - Z_i| + eta/2 (w - Z_i)^2.

    Z: (m, n); w: (..., n) broadcastable -> (..., n).
    """
    d = w[..., None, :] - Z  # (..., m, n)
    return jnp.sum(lam * jnp.abs(d) + 0.5 * eta * d * d, axis=-2)


def ens_oracle(Z: jax.Array, lam, eta) -> jax.Array:
    """Brute-force: evaluate the objective at every candidate, take argmin."""
    cands = ens_candidates(Z, lam, eta)  # (C, n)
    obj = ens_objective(Z, cands, lam, eta)  # (C, n)
    idx = jnp.argmin(obj, axis=0)  # (n,)
    return jnp.take_along_axis(cands, idx[None, :], axis=0)[0]


def ens_paper(Z: jax.Array, lam, eta) -> jax.Array:
    """Literal Algorithm 1 from the paper (first s passing the test).

    w_j(s) = mean_j - (lam/eta)(2s/m - 1), selected by
    w_desc[s] >= w_j(s) > w_desc[s+1] with w_desc[m+1] := -inf.
    As printed this returns non-minimizers in asymmetric/tied cases; see
    module docstring. Implemented faithfully for the comparison benchmark.
    """
    _check_2d(Z)
    m, n = Z.shape
    desc = -jnp.sort(-Z, axis=0)  # descending, (m, n)
    mean = jnp.mean(Z, axis=0)
    s = jnp.arange(1, m + 1, dtype=Z.dtype)
    ws = mean[None, :] - (lam / eta) * (2.0 * s[:, None] / m - 1.0)  # (m, n)
    upper = desc  # w_desc[s], s = 1..m
    lower = jnp.concatenate(
        [desc[1:], jnp.full((1, n), -jnp.inf, dtype=Z.dtype)], axis=0
    )  # w_desc[s+1]
    valid = (upper >= ws) & (ws > lower)  # (m, n)
    # first s (smallest index) passing the test, as in the paper's loop
    first = jnp.argmax(valid, axis=0)  # (n,)
    any_valid = jnp.any(valid, axis=0)
    picked = jnp.take_along_axis(ws, first[None, :], axis=0)[0]
    # the paper's loop would fall through without returning; fall back to mean
    return jnp.where(any_valid, picked, mean)


def ens_tree(tree_Z, lam, eta):
    """Apply ENS leaf-wise to a pytree whose leaves have a leading client axis.

    Each leaf has shape (m, ...); returns a pytree of leaves with shape (...).
    ENS is coordinate-wise, so reshaping to (m, -1) is exact.
    """

    return jax.tree_util.tree_map(lambda zi: ens_ref(zi, lam, eta),
                                  tree_Z)
