"""Shared helpers for Pallas kernels: padding, blocking, interpret policy."""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import lax


def default_interpret() -> bool:
    """Run kernels in interpret mode unless we are actually on TPU."""
    return jax.default_backend() != "tpu"


def u32_to_f32(u: jax.Array) -> jax.Array:
    """``u.astype(float32)`` for uint32 ``u``, in ops Mosaic can lower.

    The TPU kernel compiler has no uint32 -> float32 cast. The two 16-bit
    halves convert exactly through int32, ``hi * 2**16`` is exact, and the
    one rounding left is that of the exact sum ``u`` -- round-to-nearest,
    as in the direct cast -- so the result is bit-identical to it.
    """
    i = lax.bitcast_convert_type(u, jnp.int32)
    hi = lax.shift_right_logical(i, 16).astype(jnp.float32)
    lo = (i & 0xFFFF).astype(jnp.float32)
    return hi * 65536.0 + lo


def pad_axis(x: jax.Array, axis: int, multiple: int, value) -> jax.Array:
    """Pad ``axis`` of x up to the next multiple of ``multiple`` with ``value``."""
    size = x.shape[axis]
    target = -(-size // multiple) * multiple
    if target == size:
        return x
    widths = [(0, 0)] * x.ndim
    widths[axis] = (0, target - size)
    return jnp.pad(x, widths, constant_values=value)


def as_2d_blocks(flat: jax.Array, cols: int):
    """Reshape a 1-D array to (rows, cols), padding with zeros.

    Returns (blocked, original_size).
    """
    n = flat.shape[0]
    padded = pad_axis(flat, 0, cols, 0)
    return padded.reshape(-1, cols), n


def next_pow2(v: int) -> int:
    p = 1
    while p < v:
        p *= 2
    return p
