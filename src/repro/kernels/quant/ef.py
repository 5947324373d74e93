"""Pallas TPU kernel: fused error-feedback accumulate/compress step.

The EF21-style codec keeps a per-client memory h_i of what the server has
reconstructed so far; each round the client transmits Q(z_i - h_i) and BOTH
sides update h_i <- h_i + Q(z_i - h_i). Unfused that chain is ~8
HBM-roundtrip elementwise ops (sub, scale bcast, div, dither add, floor,
clip, mul, add); fused it is one read of (z, h, dither) and one write of
the new memory -- the same memory-bound argument as the plain quantizer
(kernels/quant/quant.py), with the residual and the accumulate folded in.

Layout is identical to the quantize kernel: the coordinate axis n is tiled
into ``block_n``-wide lane-aligned VMEM blocks, the client axis m stays
whole inside the block, and the per-row residual scale rides along as an
(m, 1) VMEM operand mapped to every block. The uint32 dither is an input --
NOT drawn in-kernel -- so the jnp reference (ef_accumulate_ref) consumes
the identical random stream and the two agree bit-for-bit. VMEM per block:
4 * m * block_n * 4 B (z, h, dither, out) -- m=128, block_n=512 -> 1 MiB,
well under the ~16 MiB budget.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels.common import default_interpret, pad_axis, u32_to_f32
from repro.kernels.quant.ref import quant_levels

_INV_2_32 = float(2.0 ** -32)


def _ef_kernel(z_ref, h_ref, u_ref, s_ref, o_ref, *, L: int,
               stochastic: bool):
    z = z_ref[...].astype(jnp.float32)          # (m, B)
    h = h_ref[...].astype(jnp.float32)          # (m, B)
    s = s_ref[...].astype(jnp.float32)          # (m, 1)
    r = z - h
    delta = s * (1.0 / L)  # mul-by-reciprocal, matching ref (see ref.py)
    safe = jnp.where(delta > 0, delta, 1.0)
    if stochastic:
        u = u32_to_f32(u_ref[...]) * _INV_2_32
    else:
        u = 0.5
    q = jnp.floor(r / safe + u)
    q = jnp.clip(q, -L, L)
    dec = jnp.where(delta > 0, q * safe, 0.0)
    o_ref[...] = (h + dec).astype(o_ref.dtype)


@functools.partial(jax.jit,
                   static_argnames=("bits", "stochastic", "block_n",
                                    "interpret"))
def _ef_call(Z, H, u32, scale, *, bits: int, stochastic: bool, block_n: int,
             interpret: bool):
    m, n = Z.shape
    L = quant_levels(bits)
    Zp = pad_axis(Z, 1, block_n, 0)
    Hp = pad_axis(H, 1, block_n, 0)
    Up = pad_axis(u32, 1, block_n, 0)
    np_ = Zp.shape[1]
    grid = (np_ // block_n,)
    blk = pl.BlockSpec((m, block_n), lambda i: (0, i))
    out = pl.pallas_call(
        functools.partial(_ef_kernel, L=L, stochastic=stochastic),
        grid=grid,
        in_specs=[blk, blk, blk, pl.BlockSpec((m, 1), lambda i: (0, 0))],
        out_specs=blk,
        out_shape=jax.ShapeDtypeStruct((m, np_), Z.dtype),
        interpret=interpret,
    )(Zp, Hp, Up, scale.reshape(m, 1))
    return out[:, :n]


def ef_accumulate_pallas(Z: jax.Array, H: jax.Array, scale: jax.Array,
                         bits: int, u32: jax.Array | None = None, *,
                         block_n: int = 512,
                         interpret: bool | None = None) -> jax.Array:
    """Fused H + Q_bits(Z - H), row-wise on the uniform ``bits``-bit grid.

    Z, H: (m, n); scale: (m,) per-row magnitude bound of the residual Z - H;
    u32: (m, n) uint32 dither (None => deterministic round-half-up).
    Semantics identical to ref.ef_accumulate_ref.
    """
    if Z.ndim != 2 or Z.shape != H.shape:
        raise ValueError(
            f"ef_accumulate_pallas expects matching (m, n); got {Z.shape} "
            f"vs {H.shape}")
    if interpret is None:
        interpret = default_interpret()
    stochastic = u32 is not None
    if u32 is None:
        u32 = jnp.zeros(Z.shape, jnp.uint32)
    return _ef_call(Z, H, u32, scale, bits=bits, stochastic=stochastic,
                    block_n=block_n, interpret=interpret)
