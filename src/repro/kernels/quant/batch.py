"""Pallas TPU kernel: batched column-bounded quantization for the codec.

Why a kernel: the fused multi-leaf upload codec (repro.sim.transport) lays
EVERY (leaf, client) pair of a pytree out as one row of a single padded
2-D array, so one kernel launch encodes the whole upload instead of one
launch per leaf. Rows differ in how many leading columns are live (the
per-leaf top-k keep count, or a dense leaf's un-padded width), so the
kernel fuses the quantize-dequantize chain with the live-column select:

    out[i, j] = Q_bits(x[i, j]; scale[i])  if j <  kcols[i]
                f[i, j]                    otherwise

Unfused that is ~8 HBM-roundtrip elementwise ops (scale bcast, div, dither
add, floor, clip, mul, iota compare, select); fused it is one read of
(x, f, dither) and one write.

Layout mirrors the row-wise quantize kernel (kernels/quant/quant.py): the
column axis n is tiled into ``block_n``-wide lane-aligned VMEM blocks, the
row axis stays whole inside the block, and the per-row (scale, kcols)
operands ride along as (m, 1) VMEM columns mapped to every block; the
global column index is reconstructed from ``pl.program_id``. The uint32
dither is an input -- NOT drawn in-kernel -- so the jnp reference
(ref.quantize_cols_ref) consumes the identical random stream and the two
agree bit-for-bit. VMEM per block: 4 * m * block_n * 4 B (x, f, dither,
out) -- m=128, block_n=512 -> 1 MiB, well under the ~16 MiB budget.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels.common import default_interpret, pad_axis, u32_to_f32
from repro.kernels.quant.ref import quant_levels

_INV_2_32 = float(2.0 ** -32)


def _quant_cols_kernel(x_ref, f_ref, u_ref, s_ref, k_ref, o_ref, *, L: int,
                       stochastic: bool, block_n: int):
    x = x_ref[...].astype(jnp.float32)          # (m, B)
    s = s_ref[...].astype(jnp.float32)          # (m, 1)
    kc = k_ref[...]                             # (m, 1) int32
    delta = s * (1.0 / L)  # mul-by-reciprocal, matching ref (see ref.py)
    safe = jnp.where(delta > 0, delta, 1.0)
    if stochastic:
        u = u32_to_f32(u_ref[...]) * _INV_2_32
    else:
        u = 0.5
    q = jnp.floor(x / safe + u)
    q = jnp.clip(q, -L, L)
    dq = jnp.where(delta > 0, q * safe, 0.0).astype(o_ref.dtype)
    col = pl.program_id(0) * block_n + jax.lax.broadcasted_iota(
        jnp.int32, x.shape, 1)
    o_ref[...] = jnp.where(col < kc, dq, f_ref[...])


@functools.partial(jax.jit,
                   static_argnames=("bits", "stochastic", "block_n",
                                    "interpret"))
def _quant_cols_call(X, F, u32, scale, kcols, *, bits: int, stochastic: bool,
                     block_n: int, interpret: bool):
    m, n = X.shape
    L = quant_levels(bits)
    Xp = pad_axis(X, 1, block_n, 0)
    Fp = pad_axis(F, 1, block_n, 0)
    Up = pad_axis(u32, 1, block_n, 0)
    np_ = Xp.shape[1]
    grid = (np_ // block_n,)
    blk = pl.BlockSpec((m, block_n), lambda i: (0, i))
    col = pl.BlockSpec((m, 1), lambda i: (0, 0))
    out = pl.pallas_call(
        functools.partial(_quant_cols_kernel, L=L, stochastic=stochastic,
                          block_n=block_n),
        grid=grid,
        in_specs=[blk, blk, blk, col, col],
        out_specs=blk,
        out_shape=jax.ShapeDtypeStruct((m, np_), X.dtype),
        interpret=interpret,
    )(Xp, Fp, Up, scale.reshape(m, 1),
      kcols.reshape(m, 1).astype(jnp.int32))
    return out[:, :n]


def quantize_cols_pallas(X: jax.Array, F: jax.Array, scale: jax.Array,
                         kcols: jax.Array, bits: int,
                         u32: jax.Array | None = None, *, block_n: int = 512,
                         interpret: bool | None = None) -> jax.Array:
    """Column-bounded quantize-dequantize with fallback substitution.

    X, F: (m, n) values and per-position fallback; scale: (m,) per-row
    magnitude bound; kcols: (m,) live-column counts -- columns j < kcols[i]
    quantize, the rest return F bit-untouched; u32: (m, n) uint32 dither
    (None => deterministic round-half-up). Semantics identical to
    ref.quantize_cols_ref.
    """
    if X.ndim != 2 or X.shape != F.shape:
        raise ValueError(
            f"quantize_cols_pallas expects matching (m, n); got {X.shape} "
            f"vs {F.shape}")
    if interpret is None:
        interpret = default_interpret()
    stochastic = u32 is not None
    if u32 is None:
        u32 = jnp.zeros(X.shape, jnp.uint32)
    return _quant_cols_call(X, F, u32, scale, kcols, bits=bits,
                            stochastic=stochastic, block_n=block_n,
                            interpret=interpret)
