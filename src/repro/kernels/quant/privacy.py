"""Pallas TPU kernel: fused clip + Laplace-noise + quantize for DP uploads.

Why a kernel: the private upload path composes three elementwise stages --
l1-clip scaling, per-client Laplace perturbation, and the column-bounded
quantize-dequantize the codec already fuses (kernels/quant/batch.py).
Run sequentially that is three HBM round-trips over the full batched
(leaf, client)-row layout; fused it is one read of (x, f, dither-q,
noise) and one write:

    y[i, j]   = x[i, j] * clipf[i] + b[i] * lap[i, j]
    out[i, j] = Q_bits(y[i, j]; scale[i])  if j <  kcols[i]
                f[i, j]                    otherwise

The per-row operands (clipf, b, scale, kcols) ride along as (m, 1) VMEM
columns mapped to every block, exactly like batch.py; the quantizer's
uint32 dither plane AND the float32 unit-Laplace plane are inputs --
NOT drawn or transformed in-kernel -- so the jnp reference
(ref.private_quantize_cols_ref) consumes the identical streams and the
two agree bit-for-bit (see the ref docstring for why the inverse-CDF
transform must stay out of fusible bodies). VMEM per block:
5 * m * block_n * 4 B (x, f, u_q, lap, out) -- m=128, block_n=512 ->
1.25 MiB, well under the ~16 MiB budget.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels.common import default_interpret, pad_axis, u32_to_f32
from repro.kernels.quant.ref import quant_levels

_INV_2_32 = float(2.0 ** -32)


def _private_cols_kernel(x_ref, f_ref, uq_ref, lap_ref, cf_ref, b_ref, s_ref,
                         k_ref, o_ref, *, L: int, block_n: int):
    x = x_ref[...].astype(jnp.float32)           # (m, B)
    cf = cf_ref[...].astype(jnp.float32)         # (m, 1)
    b = b_ref[...].astype(jnp.float32)           # (m, 1)
    s = s_ref[...].astype(jnp.float32)           # (m, 1)
    kc = k_ref[...]                              # (m, 1) int32
    lap = lap_ref[...].astype(jnp.float32)       # (m, B) unit Laplace
    y = x * cf + b * lap
    delta = s * (1.0 / L)  # mul-by-reciprocal, matching ref (see ref.py)
    safe = jnp.where(delta > 0, delta, 1.0)
    u = u32_to_f32(uq_ref[...]) * _INV_2_32
    q = jnp.floor(y / safe + u)
    q = jnp.clip(q, -L, L)
    dq = jnp.where(delta > 0, q * safe, 0.0).astype(o_ref.dtype)
    col = pl.program_id(0) * block_n + jax.lax.broadcasted_iota(
        jnp.int32, y.shape, 1)
    o_ref[...] = jnp.where(col < kc, dq, f_ref[...])


@functools.partial(jax.jit,
                   static_argnames=("bits", "block_n", "interpret"))
def _private_cols_call(X, F, u32q, lap, clipf, noise_b, scale, kcols, *,
                       bits: int, block_n: int, interpret: bool):
    m, n = X.shape
    L = quant_levels(bits)
    Xp = pad_axis(X, 1, block_n, 0)
    Fp = pad_axis(F, 1, block_n, 0)
    Uq = pad_axis(u32q, 1, block_n, 0)
    Lp = pad_axis(lap, 1, block_n, 0)
    np_ = Xp.shape[1]
    grid = (np_ // block_n,)
    blk = pl.BlockSpec((m, block_n), lambda i: (0, i))
    col = pl.BlockSpec((m, 1), lambda i: (0, 0))
    out = pl.pallas_call(
        functools.partial(_private_cols_kernel, L=L, block_n=block_n),
        grid=grid,
        in_specs=[blk, blk, blk, blk, col, col, col, col],
        out_specs=blk,
        out_shape=jax.ShapeDtypeStruct((m, np_), X.dtype),
        interpret=interpret,
    )(Xp, Fp, Uq, Lp, clipf.reshape(m, 1), noise_b.reshape(m, 1),
      scale.reshape(m, 1), kcols.reshape(m, 1).astype(jnp.int32))
    return out[:, :n]


def private_quantize_cols_pallas(X: jax.Array, F: jax.Array,
                                 clipf: jax.Array, noise_b: jax.Array,
                                 scale: jax.Array, kcols: jax.Array,
                                 bits: int, u32q: jax.Array, lap: jax.Array,
                                 *, block_n: int = 512,
                                 interpret: bool | None = None) -> jax.Array:
    """Fused clip + Laplace-noise + column-bounded quantize-dequantize.

    X, F: (m, n) values and per-position fallback; clipf, noise_b, scale:
    (m,) per-row clip factor, Laplace scale, and quantizer magnitude
    bound; kcols: (m,) live-column counts; u32q: (m, n) uint32 quantizer
    dither plane; lap: (m, n) float32 unit-Laplace noise plane (drawn by
    the caller). Semantics identical to ref.private_quantize_cols_ref.
    """
    if X.ndim != 2 or X.shape != F.shape:
        raise ValueError(
            f"private_quantize_cols_pallas expects matching (m, n); got "
            f"{X.shape} vs {F.shape}")
    if interpret is None:
        interpret = default_interpret()
    return _private_cols_call(X, F, u32q, lap, clipf, noise_b, scale,
                              kcols, bits=bits, block_n=block_n,
                              interpret=interpret)
