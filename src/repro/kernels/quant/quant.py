"""Pallas TPU kernel: uniform stochastic quantization for the upload codec.

Why a kernel: on the simulated-federation hot path every selected client's
upload is encoded each round; quantize-dequantize is purely elementwise and
memory-bound. Unfused it is ~6 HBM-roundtrip ops (scale bcast, div, dither
add, floor, clip, mul); fused it is one read of (x, dither) and one write.

Layout mirrors the ENS kernel: the coordinate axis n is tiled into
``block_n``-wide VMEM blocks (lane-aligned), the client axis m stays whole
inside the block (m is small); the per-row scale rides along as an (m, 1)
VMEM operand mapped to every block. The uint32 dither is an input -- NOT
drawn in-kernel -- so the jnp reference (kernels/quant/ref.py) consumes the
identical random stream and the two agree bit-for-bit; on-TPU PRNG would
make the codec unreproducible across backends and untestable in interpret
mode. VMEM per block: 3 * m * block_n * 4 B (x, dither, out) -- m=128,
block_n=512 -> 768 KiB, comfortably under the ~16 MiB budget.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels.common import default_interpret, pad_axis, u32_to_f32
from repro.kernels.quant.ref import quant_levels

_INV_2_32 = float(2.0 ** -32)


def _quant_kernel(x_ref, u_ref, s_ref, o_ref, *, L: int, stochastic: bool):
    x = x_ref[...].astype(jnp.float32)          # (m, B)
    s = s_ref[...].astype(jnp.float32)          # (m, 1)
    delta = s * (1.0 / L)  # mul-by-reciprocal, matching ref (see ref.py)
    safe = jnp.where(delta > 0, delta, 1.0)
    if stochastic:
        u = u32_to_f32(u_ref[...]) * _INV_2_32
    else:
        u = 0.5
    q = jnp.floor(x / safe + u)
    q = jnp.clip(q, -L, L)
    o_ref[...] = jnp.where(delta > 0, q * safe, 0.0).astype(o_ref.dtype)


@functools.partial(jax.jit,
                   static_argnames=("bits", "stochastic", "block_n",
                                    "interpret"))
def _quant_call(X, u32, scale, *, bits: int, stochastic: bool, block_n: int,
                interpret: bool):
    m, n = X.shape
    L = quant_levels(bits)
    Xp = pad_axis(X, 1, block_n, 0)
    Up = pad_axis(u32, 1, block_n, 0)
    np_ = Xp.shape[1]
    grid = (np_ // block_n,)
    blk = pl.BlockSpec((m, block_n), lambda i: (0, i))
    out = pl.pallas_call(
        functools.partial(_quant_kernel, L=L, stochastic=stochastic),
        grid=grid,
        in_specs=[blk, blk, pl.BlockSpec((m, 1), lambda i: (0, 0))],
        out_specs=blk,
        out_shape=jax.ShapeDtypeStruct((m, np_), X.dtype),
        interpret=interpret,
    )(Xp, Up, scale.reshape(m, 1))
    return out[:, :n]


def quantize_pallas(X: jax.Array, scale: jax.Array, bits: int,
                    u32: jax.Array | None = None, *, block_n: int = 512,
                    interpret: bool | None = None) -> jax.Array:
    """Quantize-dequantize X (m, n) row-wise on the uniform ``bits``-bit grid.

    scale: (m,) per-row magnitude bound; u32: (m, n) uint32 dither (None =>
    deterministic round-half-up). Semantics identical to ref.quantize_ref.
    """
    if X.ndim != 2:
        raise ValueError(f"quantize_pallas expects (m, n); got {X.shape}")
    if interpret is None:
        interpret = default_interpret()
    stochastic = u32 is not None
    if u32 is None:
        u32 = jnp.zeros(X.shape, jnp.uint32)
    return _quant_call(X, u32, scale, bits=bits, stochastic=stochastic,
                       block_n=block_n, interpret=interpret)
