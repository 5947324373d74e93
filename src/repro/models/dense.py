"""Dense GQA transformer family.

Covers: phi3-mini / phi3-medium (RoPE+SwiGLU+GQA, pre-RMSNorm),
smollm-135m (llama-arch), command-r-35b (parallel attn+ffn block, LayerNorm,
no biases), llava-next-34b (same decoder consuming patch-embedding prefixes),
hubert-xlarge (encoder-only, bidirectional attention, GELU, biases).

Three entry points per model (shared via registry):
  apply(params, batch)            -- full-sequence forward -> logits
  prefill(params, batch)          -- forward + build KV caches
  decode_step(params, state, tok) -- one token through ring/full caches

Layer stacks are scanned (leading L axis on every layer leaf).
"""
from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp
from jax import lax

from repro.models.config import ArchConfig
from repro.models.layers import (
    CacheSpec,
    apply_mlp,
    apply_norm,
    cache_append,
    cache_from_prefill,
    decode_attention,
    dense_init,
    embed_init,
    flash_attention,
    init_attention,
    init_cache,
    init_mlp,
    init_norm,
    maybe_remat,
    out_proj,
    qkv_proj,
    rope,
)
from repro.sharding.rules import constrain


# ---------------------------------------------------------------------------
# init
# ---------------------------------------------------------------------------

def init_layer(key, cfg: ArchConfig):
    ks = jax.random.split(key, 4)
    p = {
        "ln_attn": init_norm(cfg.norm, cfg.d_model, cfg.param_dtype),
        "attn": init_attention(ks[0], cfg.d_model, cfg.n_heads,
                               cfg.n_kv_heads, cfg.hd, cfg.bias,
                               cfg.param_dtype),
        "mlp": init_mlp(ks[1], cfg.d_model, cfg.d_ff, cfg.mlp, cfg.bias,
                        cfg.param_dtype),
    }
    if not cfg.parallel_block:
        p["ln_mlp"] = init_norm(cfg.norm, cfg.d_model, cfg.param_dtype)
    return p


def init(key, cfg: ArchConfig):
    k_emb, k_layers, k_out = jax.random.split(key, 3)
    layer_keys = jax.random.split(k_layers, cfg.n_layers)
    layers = jax.vmap(lambda k: init_layer(k, cfg))(layer_keys)
    params = {
        "embed": embed_init(k_emb, cfg.vocab, cfg.d_model, cfg.param_dtype),
        "layers": layers,
        "ln_f": init_norm(cfg.norm, cfg.d_model, cfg.param_dtype),
    }
    if not cfg.tie_embeddings:
        params["unembed"] = dense_init(k_out, (cfg.d_model, cfg.vocab),
                                       cfg.param_dtype)
    return params


# ---------------------------------------------------------------------------
# block
# ---------------------------------------------------------------------------

FULL, WINDOW = "full_attention", "sliding_attention"


def _attn_full(x, p, cfg: ArchConfig, positions, kind=None):
    """Attention of a layer of ``kind``: FULL (no window, rope scaled by
    ``cfg.yarn``), WINDOW (window ``cfg.sliding_window``, plain rope), or
    None in a stack whose layers are all alike (window
    ``cfg.sliding_window``, plain rope)."""
    yarn = cfg.yarn if kind == FULL else None
    window = None if kind == FULL else cfg.sliding_window
    q, k, v = qkv_proj(x, p)
    if cfg.rope_theta > 0 and cfg.attention == "causal":
        q = rope(q, positions, cfg.rope_theta, yarn)
        k = rope(k, positions, cfg.rope_theta, yarn)
    mode = "bidirectional" if cfg.attention == "bidirectional" else "causal"
    # a windowed layer of a mixed stack has a scope of its own
    scope = (jax.named_scope("attention_window") if kind == WINDOW
             else jax.named_scope("attention"))
    with scope:
        o = flash_attention(q, k, v, mode=mode, window=window,
                            q_positions=positions, kv_positions=positions)
    return out_proj(o, p), k, v


def scan_layers(block, x, layers, cfg: ArchConfig):
    """Apply the stacked ``layers`` (leading axis n_layers) to ``x``:
    ``block(h, lp, kind) -> h`` per layer, each rematerialised (see
    ``maybe_remat``). A uniform stack scans its layers; a stack with a
    ``cfg.layer_types`` period of P kinds scans its n_layers / P periods,
    each applying its P layers' kinds in order."""
    kinds = cfg.layer_types or (None,)
    if len(kinds) == 1:
        blk = maybe_remat(lambda h, lp: block(h, lp, kinds[0]), cfg)

        def body(h, lp):
            return blk(h, lp), None

        x, _ = lax.scan(body, x, layers)
        return x
    P = len(kinds)
    if cfg.n_layers % P or not set(kinds) <= {FULL, WINDOW}:
        raise ValueError(f"{cfg.name}: {cfg.n_layers} layers are not whole "
                         f"periods of layer kinds {FULL}/{WINDOW}: {kinds}")
    blks = [maybe_remat(lambda h, lp, kind=kind: block(h, lp, kind), cfg)
            for kind in kinds]
    periods = jax.tree_util.tree_map(
        lambda a: a.reshape((a.shape[0] // P, P) + a.shape[1:]), layers)

    def period(h, lp):
        for i, blk in enumerate(blks):
            h = blk(h, jax.tree_util.tree_map(lambda a: a[i], lp))
        return h, None

    x, _ = lax.scan(period, x, periods)
    return x


def block_forward(x, lp, cfg: ArchConfig, positions, kind=None):
    h = apply_norm(x, lp["ln_attn"], cfg.norm)
    attn_out, _, _ = _attn_full(h, lp["attn"], cfg, positions, kind)
    if cfg.parallel_block:
        mlp_out = apply_mlp(h, lp["mlp"], cfg.mlp)
        x = x + attn_out + mlp_out
    else:
        x = x + attn_out
        h2 = apply_norm(x, lp["ln_mlp"], cfg.norm)
        x = x + apply_mlp(h2, lp["mlp"], cfg.mlp)
    return constrain(x, "batch", "seq_res", "embed")


# ---------------------------------------------------------------------------
# full forward
# ---------------------------------------------------------------------------

def embed_inputs(params, batch, cfg: ArchConfig):
    """Token embedding, with optional stub-frontend prefix (vlm/audio).

    batch["tokens"]: (B, T) int32. For vlm, batch["patch_embeds"]
    (B, n_patches, d_model) is prepended (anyres tiling stub: the vision
    tower+projector output, per the assignment's carve-out). For audio,
    batch["frame_embeds"] (B, T, d_model) *replaces* token embeds.
    """
    if cfg.family == "audio":
        x = batch["frame_embeds"].astype(cfg.dtype)
        return x, jnp.arange(x.shape[1])
    tok = params["embed"][batch["tokens"]].astype(cfg.dtype)
    if cfg.family == "vlm" and "patch_embeds" in batch:
        pe = batch["patch_embeds"].astype(cfg.dtype)
        x = jnp.concatenate([pe, tok], axis=1)
    else:
        x = tok
    return x, jnp.arange(x.shape[1])


def unembed(x, params, cfg: ArchConfig):
    w = params.get("unembed")
    if w is None:
        w = params["embed"].T
    logits = jnp.einsum("btd,dv->btv", x, w.astype(x.dtype))
    logits = constrain(logits, "batch", "seq", "vocab")
    return logits * cfg.logit_scale


def hidden(params, batch, cfg: ArchConfig):
    """Forward to the final norm, WITHOUT the unembedding (for chunked CE)."""
    x, positions = embed_inputs(params, batch, cfg)
    x = scan_layers(
        lambda h, lp, kind: block_forward(h, lp, cfg, positions, kind),
        x, params["layers"], cfg)
    return apply_norm(x, params["ln_f"], cfg.norm)


def apply(params, batch, cfg: ArchConfig):
    return unembed(hidden(params, batch, cfg), params, cfg)


# ---------------------------------------------------------------------------
# prefill + decode
# ---------------------------------------------------------------------------

def _cache_spec(cfg: ArchConfig, batch_size: int, seq_len: int) -> CacheSpec:
    size = seq_len if cfg.sliding_window is None else min(
        seq_len, cfg.sliding_window)
    return CacheSpec(batch=batch_size, size=size, kv_heads=cfg.n_kv_heads,
                     head_dim=cfg.hd, dtype=cfg.dtype)


def init_decode_state(cfg: ArchConfig, batch_size: int, seq_len: int,
                      prefill_len):
    """Abstract decode state: per-layer caches with 'next' = prefill_len."""
    spec = _cache_spec(cfg, batch_size, seq_len)

    def one(_):
        c = init_cache(spec)
        return {**c, "next": jnp.broadcast_to(prefill_len, (batch_size,))}

    return {"caches": jax.vmap(one)(jnp.arange(cfg.n_layers))}


def prefill(params, batch, cfg: ArchConfig, max_len: Optional[int] = None):
    """Full forward; returns (logits, decode_state).

    ``max_len`` (static) sizes the KV cache for subsequent decode steps;
    defaults to the prompt length (no decode headroom).
    """
    x, positions = embed_inputs(params, batch, cfg)
    B, T = x.shape[0], x.shape[1]
    plen = batch.get("prefill_len", jnp.full((B,), T, jnp.int32))
    spec = _cache_spec(cfg, B, max_len or T)

    def body(h, lp):
        hn = apply_norm(h, lp["ln_attn"], cfg.norm)
        attn_out, k, v = _attn_full(hn, lp["attn"], cfg, positions)
        if cfg.parallel_block:
            h = h + attn_out + apply_mlp(hn, lp["mlp"], cfg.mlp)
        else:
            h = h + attn_out
            h2 = apply_norm(h, lp["ln_mlp"], cfg.norm)
            h = h + apply_mlp(h2, lp["mlp"], cfg.mlp)
        cache = cache_from_prefill(k, v, spec, plen)
        return constrain(h, "batch", "seq_res", "embed"), cache

    x, caches = lax.scan(body, x, params["layers"])
    x = apply_norm(x, params["ln_f"], cfg.norm)
    # serving: only the next-token logits are needed -- never materialise
    # the full (B, T, V) prefill logits
    return unembed(x[:, -1:], params, cfg), {"caches": caches}


def decode_step(params, state, batch, cfg: ArchConfig):
    """One-token decode. batch["tokens"]: (B, 1). Returns (logits, state)."""
    tok = batch["tokens"]
    x = params["embed"][tok].astype(cfg.dtype)  # (B, 1, d)
    pos = state["caches"]["next"][0]  # (B,) same for all layers
    positions = pos[:, None]  # (B, 1) absolute position of this token

    def body(h, layer_in):
        lp, cache = layer_in
        hn = apply_norm(h, lp["ln_attn"], cfg.norm)
        q, k, v = qkv_proj(hn, lp["attn"])
        if cfg.rope_theta > 0 and cfg.attention == "causal":
            q = rope(q, positions, cfg.rope_theta)
            k = rope(k, positions, cfg.rope_theta)
        cache = cache_append(cache, k, v)
        o = decode_attention(q, cache["k"], cache["v"], cache["pos"],
                             window=cfg.sliding_window, q_position=pos)
        attn_out = out_proj(o, lp["attn"])
        if cfg.parallel_block:
            h = h + attn_out + apply_mlp(hn, lp["mlp"], cfg.mlp)
        else:
            h = h + attn_out
            h2 = apply_norm(h, lp["ln_mlp"], cfg.norm)
            h = h + apply_mlp(h2, lp["mlp"], cfg.mlp)
        return h, cache

    x, caches = lax.scan(body, x, (params["layers"], state["caches"]))
    x = apply_norm(x, params["ln_f"], cfg.norm)
    return unembed(x, params, cfg), {"caches": caches}
