"""Shared neural-net layers for the assigned architectures.

Design notes:
  * Parameters are plain nested dicts; layer stacks store leaves with a
    leading L axis and are applied with ``lax.scan`` to keep HLO size (and
    512-device dry-run compile time) independent of depth.
  * Attention is a chunked online-softmax ("flash-style") implementation in
    pure jnp: scan over KV chunks with running (max, denom, acc). This bounds
    live memory to O(q_chunk * kv_chunk) scores, which is what makes the
    prefill_32k and long_500k dry-runs fit; XLA sees a scan, so
    cost_analysis still counts the full FLOPs.
  * GQA is explicit: q heads H = Hkv * R; scores are computed in grouped
    layout (B, Hkv, R, Tq, Tk) so the kv_heads axis stays shardable.
  * Sliding-window attention uses a *ring-buffer* KV cache of size window,
    giving O(window) decode state -- the sub-quadratic variant used for
    long_500k on attention archs (DESIGN.md §4).
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from repro.sharding.rules import constrain

def maybe_remat(fn, cfg):
    """Per-block rematerialisation: backward recomputes the block forward,
    so only the residual stream is stored across layers (MaxText-style)."""
    return jax.remat(fn) if getattr(cfg, "remat", False) else fn


# ---------------------------------------------------------------------------
# initialisers
# ---------------------------------------------------------------------------

def dense_init(key, shape, dtype=jnp.float32, scale=None):
    fan_in = shape[0] if len(shape) >= 2 else 1
    scale = scale if scale is not None else 1.0 / jnp.sqrt(fan_in)
    return (jax.random.normal(key, shape) * scale).astype(dtype)


def embed_init(key, vocab, dim, dtype=jnp.float32):
    return (jax.random.normal(key, (vocab, dim)) * 0.02).astype(dtype)


# ---------------------------------------------------------------------------
# norms
# ---------------------------------------------------------------------------

def rms_norm(x, scale, eps=1e-6):
    var = jnp.mean(jnp.square(x.astype(jnp.float32)), axis=-1, keepdims=True)
    y = x.astype(jnp.float32) * lax.rsqrt(var + eps)
    return (y * (1.0 + scale.astype(jnp.float32))).astype(x.dtype)


def layer_norm(x, scale, bias, eps=1e-5):
    xf = x.astype(jnp.float32)
    mu = jnp.mean(xf, axis=-1, keepdims=True)
    var = jnp.var(xf, axis=-1, keepdims=True)
    y = (xf - mu) * lax.rsqrt(var + eps)
    y = y * (1.0 + scale.astype(jnp.float32))
    if bias is not None:
        y = y + bias.astype(jnp.float32)
    return y.astype(x.dtype)


def apply_norm(x, p, kind: str):
    if kind == "rmsnorm":
        return rms_norm(x, p["scale"])
    return layer_norm(x, p["scale"], p.get("bias"))


def init_norm(kind: str, dim: int, dtype=jnp.float32):
    p = {"scale": jnp.zeros((dim,), dtype)}
    if kind == "layernorm":
        p["bias"] = jnp.zeros((dim,), dtype)
    return p


# ---------------------------------------------------------------------------
# RoPE
# ---------------------------------------------------------------------------

def yarn_frequencies(head_dim: int, theta: float, yarn) -> np.ndarray:
    """(head_dim // 2,) float32 inverse frequencies of YaRN rope (HF
    ``_compute_yarn_parameters``, truncated correction range)."""
    half = head_dim // 2
    pos = theta ** (np.arange(0, head_dim, 2, dtype=np.float64) / head_dim)

    def corr_dim(rotations):
        return (head_dim * np.log(yarn.original_max_position
                                  / (rotations * 2 * np.pi))
                / (2 * np.log(theta)))

    low = max(np.floor(corr_dim(yarn.beta_fast)), 0)
    high = min(np.ceil(corr_dim(yarn.beta_slow)), head_dim - 1)
    if low == high:
        high += 0.001
    ramp = np.clip((np.arange(half) - low) / (high - low), 0.0, 1.0)
    extrapolate = 1.0 - ramp
    inv = (1.0 / (yarn.factor * pos)) * (1.0 - extrapolate) \
        + (1.0 / pos) * extrapolate
    return inv.astype(np.float32)


def rope(x, positions, theta: float = 10000.0, yarn=None):
    """x: (..., T, H, D) with D even; positions: (..., T). With ``yarn``,
    YaRN frequencies and cos and sin scaled by its attention factor."""
    d = x.shape[-1]
    half = d // 2
    if yarn is None:
        freqs = jnp.exp(
            -jnp.log(theta) * jnp.arange(0, half, dtype=jnp.float32) / half
        )  # (half,)
    else:
        freqs = jnp.asarray(yarn_frequencies(d, theta, yarn))
    ang = positions[..., None].astype(jnp.float32) * freqs  # (..., T, half)
    cos = jnp.cos(ang)[..., None, :]  # (..., T, 1, half)
    sin = jnp.sin(ang)[..., None, :]
    if yarn is not None:
        cos, sin = cos * yarn.attention_factor, sin * yarn.attention_factor
    x1, x2 = x[..., :half], x[..., half:]
    y1 = x1 * cos - x2 * sin
    y2 = x2 * cos + x1 * sin
    return jnp.concatenate([y1, y2], axis=-1).astype(x.dtype)


# ---------------------------------------------------------------------------
# flash-style chunked attention
# ---------------------------------------------------------------------------

_NEG_INF = -1e30


def _mask_bias(q_pos, kv_pos, mode: str, window: Optional[int]):
    """q_pos: (Tq,), kv_pos: (Tk,) -> additive bias (Tq, Tk)."""
    valid = kv_pos[None, :] >= 0
    if mode == "causal":
        valid &= kv_pos[None, :] <= q_pos[:, None]
    elif mode == "bidirectional":
        pass
    else:
        raise ValueError(f"unknown attention mode {mode!r}")
    if window is not None:
        valid &= (q_pos[:, None] - kv_pos[None, :]) < window
    return jnp.where(valid, 0.0, _NEG_INF)


def _flash_fwd_scan(qg, kg, vg, qp, kp, mode, window, scale):
    """Online-softmax forward. Shapes:
    qg (nq, B, qc, Hkv, R, D); kg/vg (nk, B, kc, Hkv, D); qp (nq, qc);
    kp (nk, kc). Returns out (nq, B, qc, Hkv, R, D) fp32 and
    lse (nq, B, Hkv, R, qc) fp32.
    """
    nq, B, qc, Hkv, R, D = qg.shape

    def per_q_chunk(carry, qi):
        qcb, qpos = qi

        def per_kv_chunk(acc, ki):
            m, l, o = acc
            kc_, vc_, kpos = ki
            s = jnp.einsum("bqhrd,bkhd->bhrqk", qcb, kc_) * scale
            s = s + _mask_bias(qpos, kpos, mode, window)[None, None, None]
            m_new = jnp.maximum(m, jnp.max(s, axis=-1))
            p = jnp.exp(s - m_new[..., None])
            corr = jnp.exp(m - m_new)
            l_new = l * corr + jnp.sum(p, axis=-1)
            o_new = o * corr[..., None] + jnp.einsum(
                "bhrqk,bkhd->bhrqd", p, vc_)
            return (m_new, l_new, o_new), None

        m0 = jnp.full((B, Hkv, R, qc), _NEG_INF, jnp.float32)
        l0 = jnp.zeros((B, Hkv, R, qc), jnp.float32)
        o0 = jnp.zeros((B, Hkv, R, qc, D), jnp.float32)
        (m, l, o), _ = lax.scan(per_kv_chunk, (m0, l0, o0), (kg, vg, kp))
        out = o / jnp.maximum(l, 1e-30)[..., None]   # (B,Hkv,R,qc,D)
        lse = jnp.where(l > 0, m + jnp.log(jnp.maximum(l, 1e-30)), 1e30)
        out = jnp.transpose(out, (0, 3, 1, 2, 4))     # (B,qc,Hkv,R,D)
        return carry, (out, lse)

    _, (outs, lses) = lax.scan(per_q_chunk, None, (qg, qp))
    return outs, lses


def _group(q, k, v, q_positions, kv_positions, q_chunk, kv_chunk):
    B, Tq, H, D = q.shape
    Tk, Hkv = k.shape[1], k.shape[2]
    R = H // Hkv
    nq, nk = Tq // q_chunk, Tk // kv_chunk
    qg = jnp.moveaxis(
        q.reshape(B, nq, q_chunk, Hkv, R, D), 1, 0).astype(jnp.float32)
    kg = jnp.moveaxis(
        k.reshape(B, nk, kv_chunk, Hkv, D), 1, 0).astype(jnp.float32)
    vg = jnp.moveaxis(
        v.reshape(B, nk, kv_chunk, Hkv, D), 1, 0).astype(jnp.float32)
    qp = q_positions.reshape(nq, q_chunk)
    kp = kv_positions.reshape(nk, kv_chunk)
    return qg, kg, vg, qp, kp


@functools.partial(jax.custom_vjp, nondiff_argnums=(5, 6, 7, 8))
def _flash(q, k, v, q_positions, kv_positions, mode, window,
           q_chunk, kv_chunk):
    """Flash attention with O(T) residuals: the backward pass RECOMPUTES
    the (chunked) probability tiles instead of storing the T^2 attention
    matrix -- this is what makes seq-4096 training of 40-layer models fit
    HBM (and is the standard FlashAttention-2 recurrence, expressed as
    nested lax.scans so the TPU sees static control flow)."""
    qg, kg, vg, qp, kp = _group(q, k, v, q_positions, kv_positions,
                                q_chunk, kv_chunk)
    scale = 1.0 / jnp.sqrt(q.shape[-1])
    outs, _ = _flash_fwd_scan(qg, kg, vg, qp, kp, mode, window, scale)
    B, Tq, H, D = q.shape
    nq = Tq // q_chunk
    return jnp.moveaxis(outs, 0, 1).reshape(B, Tq, H, D).astype(q.dtype)


def _flash_fwd(q, k, v, q_positions, kv_positions, mode, window,
               q_chunk, kv_chunk):
    qg, kg, vg, qp, kp = _group(q, k, v, q_positions, kv_positions,
                                q_chunk, kv_chunk)
    scale = 1.0 / jnp.sqrt(q.shape[-1])
    outs, lses = _flash_fwd_scan(qg, kg, vg, qp, kp, mode, window, scale)
    B, Tq, H, D = q.shape
    out = jnp.moveaxis(outs, 0, 1).reshape(B, Tq, H, D).astype(q.dtype)
    # residuals: inputs + per-row LSE + output (O(T), never O(T^2))
    res = (q, k, v, q_positions, kv_positions, out, lses)
    return out, res


def _flash_bwd(mode, window, q_chunk, kv_chunk, res, dout):
    q, k, v, q_positions, kv_positions, out, lses = res
    B, Tq, H, D = q.shape
    Tk, Hkv = k.shape[1], k.shape[2]
    R = H // Hkv
    scale = 1.0 / jnp.sqrt(D)
    qg, kg, vg, qp, kp = _group(q, k, v, q_positions, kv_positions,
                                q_chunk, kv_chunk)
    nq, nk = Tq // q_chunk, Tk // kv_chunk
    dog = jnp.moveaxis(
        dout.reshape(B, nq, q_chunk, Hkv, R, D), 1, 0).astype(jnp.float32)
    og = jnp.moveaxis(
        out.reshape(B, nq, q_chunk, Hkv, R, D), 1, 0).astype(jnp.float32)

    def per_q_chunk(carry, xs):
        dk_acc, dv_acc = carry
        qcb, docb, ocb, lse_cb, qpos = xs
        # D_i = sum_d dout_i * out_i, computed PER CHUNK: the big
        # (nq, B, qc, H, D) einsum outside the scan hits an SPMD layout
        # transition the partitioner can only solve by full replication
        # ("involuntary full rematerialization", ~9 GB/device of gathers
        # on smollm train -- EXPERIMENTS.md §Perf 1.5)
        Dcb = jnp.einsum("bqhrd,bqhrd->bhrq", docb, ocb)

        def per_kv_chunk(dq, ki):
            kc_, vc_, kpos = ki
            s = jnp.einsum("bqhrd,bkhd->bhrqk", qcb, kc_) * scale
            s = s + _mask_bias(qpos, kpos, mode, window)[None, None, None]
            p = jnp.exp(s - lse_cb[..., None])            # (B,Hkv,R,qc,kc)
            dp = jnp.einsum("bqhrd,bkhd->bhrqk", docb, vc_)
            ds = p * (dp - Dcb[..., None])
            dq = dq + jnp.einsum("bhrqk,bkhd->bqhrd", ds, kc_) * scale
            dk_c = jnp.einsum("bhrqk,bqhrd->bkhd", ds, qcb) * scale
            dv_c = jnp.einsum("bhrqk,bqhrd->bkhd", p, docb)
            return dq, (dk_c, dv_c)

        dq0 = jnp.zeros_like(qcb)
        dq, (dk_cs, dv_cs) = lax.scan(per_kv_chunk, dq0, (kg, vg, kp))
        return (dk_acc + dk_cs, dv_acc + dv_cs), dq

    dk0 = jnp.zeros((nk, B, kv_chunk, Hkv, D), jnp.float32)
    dv0 = jnp.zeros((nk, B, kv_chunk, Hkv, D), jnp.float32)
    (dkg, dvg), dqg = lax.scan(per_q_chunk, (dk0, dv0),
                               (qg, dog, og, lses, qp))
    dq = jnp.moveaxis(dqg, 0, 1).reshape(B, Tq, H, D).astype(q.dtype)
    dk = jnp.moveaxis(dkg, 0, 1).reshape(B, Tk, Hkv, D).astype(k.dtype)
    dv = jnp.moveaxis(dvg, 0, 1).reshape(B, Tk, Hkv, D).astype(v.dtype)
    zq = np.zeros(q_positions.shape, jax.dtypes.float0)
    zk = np.zeros(kv_positions.shape, jax.dtypes.float0)
    return dq, dk, dv, zq, zk


_flash.defvjp(_flash_fwd, _flash_bwd)


def flash_attention(q, k, v, *, mode="causal", window=None,
                    q_positions=None, kv_positions=None,
                    q_chunk=512, kv_chunk=1024):
    """Chunked online-softmax attention with GQA and an O(T)-memory
    custom VJP. q: (B, Tq, H, D); k, v: (B, Tk, Hkv, D); H = Hkv * R.
    Returns (B, Tq, H, D) in q.dtype.
    """
    B, Tq, H, D = q.shape
    Tk = k.shape[1]
    if q_positions is None:
        q_positions = jnp.arange(Tq)
    if kv_positions is None:
        kv_positions = jnp.arange(Tk)
    q_chunk = min(q_chunk, Tq)
    kv_chunk = min(kv_chunk, Tk)
    # pad to chunk multiples; padded kv positions are -1 => masked out;
    # padded q rows are sliced away after
    pq = (-Tq) % q_chunk
    pk = (-Tk) % kv_chunk
    if pq:
        q = jnp.pad(q, ((0, 0), (0, pq), (0, 0), (0, 0)))
        q_positions = jnp.pad(q_positions, (0, pq), constant_values=0)
    if pk:
        k = jnp.pad(k, ((0, 0), (0, pk), (0, 0), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, pk), (0, 0), (0, 0)))
        kv_positions = jnp.pad(kv_positions, (0, pk), constant_values=-1)
    out = _flash(q, k, v, q_positions, kv_positions, mode, window,
                 q_chunk, kv_chunk)
    return out[:, :Tq]


def decode_attention(q1, cache_k, cache_v, kv_positions, *,
                     window=None, q_position=None):
    """Single-step decode: q1 (B, 1, H, D) over a (possibly ring) cache.

    cache_k/v: (B, S, Hkv, D); kv_positions: (B, S) absolute positions,
    -1 for unwritten slots. Ring semantics are encoded entirely in
    kv_positions, so full and sliding-window caches share this path.
    """
    B, S, Hkv, D = cache_k.shape
    H = q1.shape[2]
    R = H // Hkv
    scale = 1.0 / jnp.sqrt(D)
    qg = q1.reshape(B, Hkv, R, D).astype(jnp.float32)
    s = jnp.einsum("bhrd,bkhd->bhrk", qg, cache_k.astype(jnp.float32)) * scale
    valid = kv_positions >= 0
    if q_position is not None:
        valid &= kv_positions <= q_position[:, None]
        if window is not None:
            valid &= (q_position[:, None] - kv_positions) < window
    s = jnp.where(valid[:, None, None, :], s, _NEG_INF)
    p = jax.nn.softmax(s, axis=-1)
    o = jnp.einsum("bhrk,bkhd->bhrd", p, cache_v.astype(jnp.float32))
    return o.reshape(B, 1, H, D).astype(q1.dtype)


# ---------------------------------------------------------------------------
# KV cache (full or ring/sliding-window)
# ---------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class CacheSpec:
    batch: int
    size: int          # slots: full seq_len, or window for SWA
    kv_heads: int
    head_dim: int
    dtype: object = jnp.bfloat16


def init_cache(spec: CacheSpec):
    return {
        "k": jnp.zeros((spec.batch, spec.size, spec.kv_heads, spec.head_dim),
                       spec.dtype),
        "v": jnp.zeros((spec.batch, spec.size, spec.kv_heads, spec.head_dim),
                       spec.dtype),
        "pos": jnp.full((spec.batch, spec.size), -1, jnp.int32),
        "next": jnp.zeros((spec.batch,), jnp.int32),  # absolute next position
    }


def cache_append(cache, k1, v1):
    """Append one token (B, 1, Hkv, D) at slot next % size (ring)."""
    B, S = cache["pos"].shape
    nxt = cache["next"]  # (B,)
    slot = nxt % S
    bidx = jnp.arange(B)
    k = cache["k"].at[bidx, slot].set(k1[:, 0].astype(cache["k"].dtype))
    v = cache["v"].at[bidx, slot].set(v1[:, 0].astype(cache["v"].dtype))
    pos = cache["pos"].at[bidx, slot].set(nxt)
    return {"k": k, "v": v, "pos": pos, "next": nxt + 1}


def cache_from_prefill(k, v, spec: CacheSpec, prefill_len):
    """Build a cache from full prefill K/V (B, T, Hkv, D), keeping the last
    ``size`` entries (ring layout: slot = pos % size)."""
    B, T = k.shape[0], k.shape[1]
    S = spec.size
    cache = init_cache(spec)
    if T <= S:
        kpad = jnp.pad(k, ((0, 0), (0, S - T), (0, 0), (0, 0)))
        vpad = jnp.pad(v, ((0, 0), (0, S - T), (0, 0), (0, 0)))
        pos = jnp.where(jnp.arange(S)[None, :] < prefill_len[:, None],
                        jnp.arange(S)[None, :], -1)
        return {"k": kpad.astype(spec.dtype), "v": vpad.astype(spec.dtype),
                "pos": pos.astype(jnp.int32),
                "next": prefill_len.astype(jnp.int32)}
    # keep last S tokens; ring slot of absolute position p is p % S
    tail_k = k[:, T - S:]
    tail_v = v[:, T - S:]
    abs_pos = jnp.arange(T - S, T)[None, :] * jnp.ones((B, 1), jnp.int32)
    slot = abs_pos % S
    bidx = jnp.arange(B)[:, None]
    ck = jnp.zeros((B, S) + k.shape[2:], spec.dtype)
    cv = jnp.zeros((B, S) + v.shape[2:], spec.dtype)
    ck = ck.at[bidx, slot].set(tail_k.astype(spec.dtype))
    cv = cv.at[bidx, slot].set(tail_v.astype(spec.dtype))
    pos = jnp.full((B, S), -1, jnp.int32).at[bidx, slot].set(abs_pos)
    return {"k": ck, "v": cv, "pos": pos,
            "next": prefill_len.astype(jnp.int32)}


# ---------------------------------------------------------------------------
# MLPs
# ---------------------------------------------------------------------------

def init_mlp(key, d_model, d_ff, kind="swiglu", bias=False, dtype=jnp.float32):
    ks = jax.random.split(key, 3)
    p = {}
    if kind == "swiglu":
        p["wi"] = dense_init(ks[0], (d_model, d_ff), dtype)
        p["wg"] = dense_init(ks[1], (d_model, d_ff), dtype)
    else:  # gelu
        p["wi"] = dense_init(ks[0], (d_model, d_ff), dtype)
    p["wo"] = dense_init(ks[2], (d_ff, d_model), dtype)
    if bias:
        p["bi"] = jnp.zeros((d_ff,), dtype)
        p["bo"] = jnp.zeros((d_model,), dtype)
    return p


def apply_mlp(x, p, kind="swiglu"):
    dt = x.dtype
    if kind == "swiglu":
        h = jax.nn.silu(x @ p["wi"].astype(dt)) * (x @ p["wg"].astype(dt))
    else:
        h = x @ p["wi"].astype(dt)
        if "bi" in p:
            h = h + p["bi"].astype(dt)
        h = jax.nn.gelu(h)
    h = constrain(h, "batch", "seq", "mlp")
    y = h @ p["wo"].astype(dt)
    if "bo" in p:
        y = y + p["bo"].astype(dt)
    return y


# ---------------------------------------------------------------------------
# Attention projections
# ---------------------------------------------------------------------------

def init_attention(key, d_model, n_heads, n_kv_heads, head_dim, bias=False,
                   dtype=jnp.float32):
    ks = jax.random.split(key, 4)
    p = {
        "wq": dense_init(ks[0], (d_model, n_heads, head_dim), dtype),
        "wk": dense_init(ks[1], (d_model, n_kv_heads, head_dim), dtype),
        "wv": dense_init(ks[2], (d_model, n_kv_heads, head_dim), dtype),
        "wo": dense_init(ks[3], (n_heads, head_dim, d_model), dtype),
    }
    if bias:
        p["bq"] = jnp.zeros((n_heads, head_dim), dtype)
        p["bk"] = jnp.zeros((n_kv_heads, head_dim), dtype)
        p["bv"] = jnp.zeros((n_kv_heads, head_dim), dtype)
        p["bo"] = jnp.zeros((d_model,), dtype)
    return p


def qkv_proj(x, p):
    dt = x.dtype
    q = jnp.einsum("btd,dhk->bthk", x, p["wq"].astype(dt))
    k = jnp.einsum("btd,dhk->bthk", x, p["wk"].astype(dt))
    v = jnp.einsum("btd,dhk->bthk", x, p["wv"].astype(dt))
    if "bq" in p:
        q = q + p["bq"].astype(dt)
        k = k + p["bk"].astype(dt)
        v = v + p["bv"].astype(dt)
    q = constrain(q, "batch", "seq", "heads", None)
    k = constrain(k, "batch", "seq", "kv_heads", None)
    v = constrain(v, "batch", "seq", "kv_heads", None)
    return q, k, v


def out_proj(attn_out, p):
    dt = attn_out.dtype
    y = jnp.einsum("bthk,hkd->btd", attn_out, p["wo"].astype(dt))
    if "bo" in p:
        y = y + p["bo"].astype(dt)
    return y
