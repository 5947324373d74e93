"""Public jit'd entry points for ENS with kernel/reference dispatch.

``ens(Z, lam, eta)``        -- (m, n) -> (n,), picks Pallas kernel or jnp ref.
``ens_tree(tree, lam, eta)`` -- leaf-wise over a pytree with leading client axis.
"""
from __future__ import annotations

import numbers
from typing import Literal

import jax

from repro.kernels.ens import ref as _ref
from repro.kernels.ens.ens import ens_pallas

Impl = Literal["pallas", "ref", "oracle"]

# the sort path: leaves above this many elements are processed in lax.map
# chunks over their axis 1 (the stacked-layer axis of a layer stack, the
# vocabulary of an embedding), so the (2m+1)-stacked sort buffer of a
# 30 GB MoE leaf never materialises at once (it also SERIALISES the
# per-chunk sorts -- without it the scheduler overlaps every leaf's sort
# and the transient peak is sum-of-leaves)
_CHUNK_THRESHOLD = 1 << 24

# the selection path up to this many clients: its network grows as
# m log^2 m min/max ops a coordinate. Compiled for a v5e on a (m, 30, 2^15)
# leaf, the selection is one fusion that XLA's cost analysis counts below
# the sort at m = 8 and 16 (281 vs 788 MB accessed, 554 vs 1,291 MB), but
# at m = 50 it splits into 8 fusions that access 6.6 GB and hold 429 MB of
# temporaries, against the sort's 0.55 GB and 234 MB. benchmarks/ens_paths.py
# times both paths on the chip.
_SELECT_MAX_M = 8


def _selects(m: int, lam, eta) -> bool:
    """Whether ENS takes ``ens_select``: few clients, and candidates that
    rise with a, which a non-negative lam/eta known at trace time shows."""
    return (m <= _SELECT_MAX_M
            and isinstance(lam, numbers.Real) and isinstance(eta, numbers.Real)
            and lam / eta >= 0)


def _ens_ref_chunked(z, lam, eta):
    import jax.numpy as jnp
    from jax import lax

    if z.size <= _CHUNK_THRESHOLD or z.ndim < 2 or z.shape[1] < 2:
        return _ref.ens_ref(z, lam, eta)
    zs = jnp.moveaxis(z, 1, 0)  # (L, m, ...)
    out = lax.map(lambda zl: _ref.ens_ref(zl, lam, eta), zs)
    return out  # (L, ...) == the leaf layout with the client axis removed


def ens(Z: jax.Array, lam, eta, *, impl: Impl = "pallas",
        block_n: int = 512, interpret: bool | None = None) -> jax.Array:
    if impl == "pallas":
        return ens_pallas(Z, lam, eta, block_n=block_n, interpret=interpret)
    if impl == "ref":
        if _selects(Z.shape[0], lam, eta):
            return _ref.ens_select(Z, lam, eta)
        return _ref.ens_ref(Z, lam, eta)
    if impl == "oracle":
        return _ref.ens_oracle(Z, lam, eta)
    raise ValueError(f"unknown ENS impl {impl!r}")


def ens_tree(tree_Z, lam, eta, *, impl: Impl = "ref", block_n: int = 512,
             interpret: bool | None = None):
    """Leaf-wise ENS. Each leaf (m, ...) -> (...). Coordinate-wise, so exact.

    The "ref" path works along axis 0 WITHOUT flattening (a (m, -1) reshape
    of a sharded leaf is unrepresentable under SPMD and would replicate):
    up to ``_SELECT_MAX_M`` clients it selects the median by min/max
    (``ens_select``, one elementwise pass over the whole leaf), above that
    it sorts (``_ens_ref_chunked``). The Pallas path flattens -- it runs on
    local 2-D blocks (shard_map or single device), where the reshape is
    free.
    """
    if impl == "ref":
        def per_leaf_ref(z):
            if _selects(z.shape[0], lam, eta):
                return _ref.ens_select(z, lam, eta).astype(z.dtype)
            return _ens_ref_chunked(z, lam, eta).astype(z.dtype)

        return jax.tree_util.tree_map(per_leaf_ref, tree_Z)

    def per_leaf(z):
        m = z.shape[0]
        out = ens(z.reshape(m, -1), lam, eta, impl=impl, block_n=block_n,
                  interpret=interpret)
        return out.reshape(z.shape[1:]).astype(z.dtype)

    return jax.tree_util.tree_map(per_leaf, tree_Z)
