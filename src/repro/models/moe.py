"""Mixture-of-Experts transformer (Mixtral family: 8 experts, top-2, SWA;
Mellum2: 64 experts, top-8, a period of windowed and full layers).

Routing is capacity-bounded and sort-based (dropless up to the capacity
factor): token assignments are argsorted by expert, positions within each
expert computed from exclusive-cumsum group starts, and tokens beyond
capacity C = cf * top_k * T / E are dropped (weight renormalised). The
per-expert compute is ONE batched matmul over a dense (E, C, d) buffer, so
HLO_FLOPs ~= cf * top_k * (dense-equivalent FLOPs) and the roofline's
MODEL_FLOPS/HLO_FLOPs ratio stays honest (DESIGN.md §5). Expert weights are
(E, d, ff) with ff sharded over "model"; the dispatch buffer shards over
("pod","data") like the tokens it came from.

A layer told which experts it holds (``cfg.experts_held``, expert
parallelism: this chip's share of the n_experts) routes over all
n_experts, keeps each token's top_k, and computes only the held experts'
part of the result, DROPLESS: every held expert runs on every token,
weighted by the token's gate (``held_moe_mlp``), so no assignment is
lost however uneven the load. What the absent experts would add is not
computed here; on one chip that partial result goes to the next layer.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import lax

from repro.models import dense
from repro.models.config import ArchConfig
from repro.models.layers import (
    apply_norm,
    cache_append,
    cache_from_prefill,
    decode_attention,
    dense_init,
    init_attention,
    init_norm,
    out_proj,
    qkv_proj,
    rope,
)
from repro.sharding.rules import constrain


def init_moe_mlp(key, cfg: ArchConfig):
    ks = jax.random.split(key, 4)
    E, d, ff = cfg.n_experts, cfg.d_model, cfg.d_ff
    if cfg.experts_held:
        # the held experts only, each scaled by its own fan-in
        H = len(cfg.experts_held)
        return {
            "router": dense_init(ks[0], (d, E), cfg.param_dtype),
            "wi": dense_init(ks[1], (H, d, ff), cfg.param_dtype, d ** -0.5),
            "wg": dense_init(ks[2], (H, d, ff), cfg.param_dtype, d ** -0.5),
            "wo": dense_init(ks[3], (H, ff, d), cfg.param_dtype, ff ** -0.5),
        }
    return {
        "router": dense_init(ks[0], (d, E), cfg.param_dtype),
        "wi": dense_init(ks[1], (E, d, ff), cfg.param_dtype),
        "wg": dense_init(ks[2], (E, d, ff), cfg.param_dtype),
        "wo": dense_init(ks[3], (E, ff, d), cfg.param_dtype),
    }


def init_layer(key, cfg: ArchConfig):
    ks = jax.random.split(key, 2)
    return {
        "ln_attn": init_norm(cfg.norm, cfg.d_model, cfg.param_dtype),
        "attn": init_attention(ks[0], cfg.d_model, cfg.n_heads,
                               cfg.n_kv_heads, cfg.hd, cfg.bias,
                               cfg.param_dtype),
        "ln_mlp": init_norm(cfg.norm, cfg.d_model, cfg.param_dtype),
        "moe": init_moe_mlp(ks[1], cfg),
    }


def init(key, cfg: ArchConfig):
    k_emb, k_layers, k_out = jax.random.split(key, 3)
    layer_keys = jax.random.split(k_layers, cfg.n_layers)
    layers = jax.vmap(lambda k: init_layer(k, cfg))(layer_keys)
    return {
        "embed": dense.embed_init(k_emb, cfg.vocab, cfg.d_model,
                                  cfg.param_dtype),
        "layers": layers,
        "ln_f": init_norm(cfg.norm, cfg.d_model, cfg.param_dtype),
        "unembed": dense_init(k_out, (cfg.d_model, cfg.vocab),
                              cfg.param_dtype),
    }


def moe_mlp(x, p, cfg: ArchConfig):
    """x: (B, T, d) -> (B, T, d), plus aux metrics dict.

    With ``cfg.experts_held``: the held experts' dropless share
    (``held_moe_mlp``), no aux.

    When the ambient sharding rules map "batch" onto G > 1 mesh shards,
    routing/dispatch runs PER SHARD (vmap + spmd_axis_name) with capacity
    C/G each: tokens never cross data shards for dispatch, so the global
    argsort does not force an all-gather of the token stream. (Same
    approximation every capacity-based TPU MoE makes; the capacity factor
    absorbs the extra imbalance. Documented in DESIGN.md.)"""
    from repro.sharding.rules import batch_groups
    B, T, d = x.shape
    if cfg.experts_held:
        out, _ = held_moe_mlp(x.reshape(B * T, d), p, cfg)
        return out.reshape(B, T, d), {}
    N = B * T
    xf = x.reshape(N, d)
    G, gaxes = batch_groups()
    # group-dispatch only for bulk token streams: for tiny N (decode) the
    # G-way split would pin the "data" axis to token groups and force XLA
    # to ALL-GATHER the data-sharded expert weights instead of
    # partial-summing activations (measured 201 MB x n_layers per decode
    # step on mixtral-8x22b, EXPERIMENTS.md §Perf 1.3)
    if G > 1 and N % G == 0 and (N // G) >= 64:
        xg = xf.reshape(G, N // G, d)
        yg, aux = jax.vmap(
            lambda xx: _moe_dispatch(xx, p, cfg),
            spmd_axis_name=(gaxes if len(gaxes) > 1 else gaxes[0]))(xg)
        aux = jax.tree_util.tree_map(jnp.mean, aux)
        return yg.reshape(B, T, d), aux
    out, aux = _moe_dispatch(xf, p, cfg)
    return out.reshape(B, T, d), aux


def _moe_dispatch(xf, p, cfg: ArchConfig):
    """Capacity-bounded sort-based dispatch for one token block (N, d)."""
    N, d = xf.shape
    E, K = cfg.n_experts, cfg.top_k

    logits = (xf @ p["router"].astype(xf.dtype)).astype(jnp.float32)  # (N, E)
    probs = jax.nn.softmax(logits, axis=-1)
    top_p, top_e = lax.top_k(probs, K)  # (N, K)
    top_p = top_p / jnp.maximum(jnp.sum(top_p, axis=-1, keepdims=True), 1e-9)

    # flatten the K assignments
    e_all = top_e.reshape(-1)            # (N*K,)
    p_all = top_p.reshape(-1)
    src = jnp.repeat(jnp.arange(N), K)   # source token of each assignment

    order = jnp.argsort(e_all)           # group by expert
    e_sorted = e_all[order]
    src_sorted = src[order]
    p_sorted = p_all[order]

    counts = jnp.bincount(e_all, length=E)            # (E,)
    starts = jnp.cumsum(counts) - counts              # exclusive cumsum
    pos_in_expert = jnp.arange(N * K) - starts[e_sorted]

    C = max(1, int(cfg.capacity_factor * K * N / E))
    keep = pos_in_expert < C
    slot = e_sorted * C + jnp.minimum(pos_in_expert, C - 1)

    # dispatch into (E*C, d)
    buf = jnp.zeros((E * C, d), xf.dtype)
    vals = jnp.where(keep[:, None], xf[src_sorted], 0.0)
    buf = buf.at[slot].add(jnp.where(keep[:, None], vals, 0.0))
    buf = buf.reshape(E, C, d)
    buf = constrain(buf, "experts", None, "embed")

    # expert FFN: batched matmuls (E, C, d) x (E, d, ff)
    wi = p["wi"].astype(xf.dtype)
    wg = p["wg"].astype(xf.dtype)
    wo = p["wo"].astype(xf.dtype)
    h = jax.nn.silu(jnp.einsum("ecd,edf->ecf", buf, wi)) * jnp.einsum(
        "ecd,edf->ecf", buf, wg)
    h = constrain(h, "experts", None, "mlp")
    y = jnp.einsum("ecf,efd->ecd", h, wo).reshape(E * C, d)

    # combine back
    gathered = y[slot] * p_sorted[:, None].astype(xf.dtype)
    gathered = jnp.where(keep[:, None], gathered, 0.0)
    out = jnp.zeros((N, d), xf.dtype).at[src_sorted].add(gathered)

    # aux: load-balance loss ingredients (Switch-style)
    me = jnp.mean(probs, axis=0)                       # mean router prob
    ce = jnp.bincount(e_all, length=E) / (N * K)       # fraction routed
    aux = {"lb_loss": E * jnp.sum(me * ce),
           "dropped": 1.0 - jnp.mean(keep.astype(jnp.float32))}
    return out, aux


# ---------------------------------------------------------------------------
# held experts: every held expert on every token, gated
# ---------------------------------------------------------------------------

def route_held(xf, router, cfg: ArchConfig):
    """Route (N, d) tokens over all n_experts and keep each one's top_k,
    their softmax weights renormalised over the top_k (HF's
    ``norm_topk_prob``); -> (gates (N, held) float32, each held expert's
    weight for each token, 0 where the token did not choose it; load
    (held,) int32, the tokens that chose each held expert).

    The logits are float32 at the highest matmul precision, so that
    low-precision rounding flips as few top-k choices as it can, and the
    top-k is taken of the logits: the softmax's order, without the ties of
    probabilities that underflow to 0."""
    logits = jnp.dot(xf.astype(jnp.float32), router.astype(jnp.float32),
                     precision=lax.Precision.HIGHEST)
    _, top_e = lax.top_k(logits, cfg.top_k)
    top_p = jnp.take_along_axis(jax.nn.softmax(logits, axis=-1), top_e, -1)
    top_p = top_p / jnp.sum(top_p, axis=-1, keepdims=True)
    chosen = top_e[..., None] == jnp.asarray(cfg.experts_held)  # (N, k, h)
    gates = jnp.sum(jnp.where(chosen, top_p[..., None], 0.0), axis=1)
    return gates, jnp.sum(chosen, axis=(0, 1), dtype=jnp.int32)


def held_moe_mlp(xf, p, cfg: ArchConfig):
    """The held experts' share of the layer for (N, d) tokens -> ((N, d),
    load (held,) int32: the tokens each held expert serves).

    Dropless with no dispatch: every held expert runs on every token, its
    hidden activation scaled by the token's gate (0 where the token did
    not choose it), and the held experts' down projections sum in one
    contraction over (held, d_ff). No sort, gather or scatter runs, and
    the cost does not move with the routing, however uneven: a sorted
    dispatch computes only the routed rows (a top_k / n_experts share of
    these, 1 in 8 in Mellum2's), but its rate follows the load."""
    dt = xf.dtype
    with jax.named_scope("moe_route"):
        gates, load = route_held(xf, p["router"], cfg)
    with jax.named_scope("moe_experts"):
        h = jax.nn.silu(jnp.einsum("nd,edf->nef", xf, p["wi"].astype(dt))) \
            * jnp.einsum("nd,edf->nef", xf, p["wg"].astype(dt))
        out = jnp.einsum("nef,efd->nd", h * gates.astype(dt)[..., None],
                         p["wo"].astype(dt))
    return out, load


def block_forward(x, lp, cfg: ArchConfig, positions, kind=None):
    h = apply_norm(x, lp["ln_attn"], cfg.norm)
    attn_out, k, v = dense._attn_full(h, lp["attn"], cfg, positions, kind)
    x = x + attn_out
    h2 = apply_norm(x, lp["ln_mlp"], cfg.norm)
    mlp_out, aux = moe_mlp(h2, lp["moe"], cfg)
    x = x + mlp_out
    return constrain(x, "batch", "seq_res", "embed"), (k, v, aux)


def hidden(params, batch, cfg: ArchConfig):
    x, positions = dense.embed_inputs(params, batch, cfg)
    x = dense.scan_layers(
        lambda h, lp, kind: block_forward(h, lp, cfg, positions, kind)[0],
        x, params["layers"], cfg)
    return apply_norm(x, params["ln_f"], cfg.norm)


def apply(params, batch, cfg: ArchConfig):
    return dense.unembed(hidden(params, batch, cfg), params, cfg)


def prefill(params, batch, cfg: ArchConfig, max_len=None):
    x, positions = dense.embed_inputs(params, batch, cfg)
    B, T = x.shape[0], x.shape[1]
    plen = batch.get("prefill_len", jnp.full((B,), T, jnp.int32))
    spec = dense._cache_spec(cfg, B, max_len or T)

    def body(h, lp):
        h, (k, v, _) = block_forward(h, lp, cfg, positions)
        return h, cache_from_prefill(k, v, spec, plen)

    x, caches = lax.scan(body, x, params["layers"])
    x = apply_norm(x, params["ln_f"], cfg.norm)
    return dense.unembed(x[:, -1:], params, cfg), {"caches": caches}


init_decode_state = dense.init_decode_state


def decode_step(params, state, batch, cfg: ArchConfig):
    tok = batch["tokens"]
    x = params["embed"][tok].astype(cfg.dtype)
    pos = state["caches"]["next"][0]
    positions = pos[:, None]

    def body(h, layer_in):
        lp, cache = layer_in
        hn = apply_norm(h, lp["ln_attn"], cfg.norm)
        q, k, v = qkv_proj(hn, lp["attn"])
        if cfg.rope_theta > 0:
            q = rope(q, positions, cfg.rope_theta)
            k = rope(k, positions, cfg.rope_theta)
        cache = cache_append(cache, k, v)
        o = decode_attention(q, cache["k"], cache["v"], cache["pos"],
                             window=cfg.sliding_window, q_position=pos)
        h = h + out_proj(o, lp["attn"])
        h2 = apply_norm(h, lp["ln_mlp"], cfg.norm)
        mlp_out, _ = moe_mlp(h2, lp["moe"], cfg)
        h = h + mlp_out
        return h, cache

    x, caches = lax.scan(body, x, (params["layers"], state["caches"]))
    x = apply_norm(x, params["ln_f"], cfg.norm)
    return dense.unembed(x, params, cfg), {"caches": caches}
