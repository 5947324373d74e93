"""Plain reference of one chip's share of Mellum2-12B-A2.5B
(JetBrains/Mellum2-12B-A2.5B-Instruct config.json).

A decoder of pre-norm blocks: RMSNorm -> grouped-query attention (32 query
heads over 4 key/value heads of width 128, so the query width 4096 exceeds
the residual's 2304) with rotary positions (rotate-half form) -> residual,
RMSNorm -> sparse mixture of experts -> residual, then a final RMSNorm and
an untied head. ``layer_types`` sets each layer's attention: a
``sliding_attention`` layer sees the keys less than ``sliding_window``
positions back with plain rope (theta 500,000); a ``full_attention`` layer
sees every earlier key with YaRN rope (HF ``rope_type: "yarn"``:
frequencies blended between interpolation and extrapolation by a linear
ramp over the truncated correction range of ``beta_fast`` and ``beta_slow``
rotations in ``original_max_position_embeddings`` tokens, cos and sin
scaled by ``attention_factor``). The expert layer scores all 64 experts
by a softmax of the router's logits, keeps each token's top 8 and
renormalises their weights over those 8 (``norm_topk_prob``); an expert
is a SwiGLU MLP of width 896. Each RMSNorm weight is kept as (1 + scale)
with scale starting at 0. Written in float32 at the highest matmul
precision, with plain softmax attention computed in blocks of query rows
and the head's logits in blocks of sequence rows.

Departures from the published description, all of them the chip's share
or what the config leaves open (the configuration's JSON states each):

* depth: the first period of ``layer_types``, ``num_hidden_layers`` of
  them;
* experts: the router keeps its published 64 outputs and top-8, and only
  the held experts (``experts_held``) add to the layer's output; what the
  other experts would add is left out. Each held expert is computed for
  every token and weighted by its gate, which is zero where the token did
  not choose it;
* vocabulary: the first ``vocab_size`` rows of the published 98,304;
  tokens are drawn from that slice and the loss is over it;
* assumed: softmax router scoring, no q/k norm, no attention bias, and no
  "MTP head" (unconfirmed in the model's description; the main head only).

The control computes every matmul on operands rounded to float8 e4m3, one
step below the configuration's bfloat16; the half-batch fault takes
each client's loss over the first half of its one sequence. Weights: the
program's seeded draw (fan-in scaled normals, each expert's by its own
fan-in, embedding normal(0, 0.02)), see ``init``. Data, the control's
rounding and the state's dtype are those of ``smollm-135m.py`` beside
this file, loaded by path.
"""
from __future__ import annotations

import importlib.util
import math
import pathlib

import jax
import jax.numpy as jnp
import numpy as np

_spec = importlib.util.spec_from_file_location(
    "chipbench_config_smollm_135m_data",
    pathlib.Path(__file__).with_name("smollm-135m.py"))
_lm = importlib.util.module_from_spec(_spec)
_spec.loader.exec_module(_lm)

OBJECTIVE_EVERY_ROUND = _lm.OBJECTIVE_EVERY_ROUND
HIGHEST = jax.lax.Precision.HIGHEST
Q_BLOCK = 256                  # query rows a block of attention scores
LOSS_ROWS = 1024               # sequence rows a block of the head's logits
make_data_tokens = _lm.token_batches
state_dtype = _lm.state_dtype


def dims(cfg: dict) -> dict:
    L = cfg["num_hidden_layers"]
    return {"L": L, "d": cfg["hidden_size"],
            "H": cfg["num_attention_heads"],
            "Hkv": cfg["num_key_value_heads"], "hd": cfg["head_dim"],
            "ff": cfg["moe_intermediate_size"], "V": cfg["vocab_size"],
            "E": cfg["published"]["num_experts"],
            "held": list(cfg["experts_held"]),
            "K": cfg["num_experts_per_tok"],
            "kinds": cfg["layer_types"][:L]}


def n_params(cfg: dict) -> int:
    """Every parameter this chip holds: embedding and head, and per layer
    attention, the router (all 64 outputs), the held experts and two
    norms, and the final norm."""
    k = dims(cfg)
    d = k["d"]
    attn = d * k["hd"] * (2 * k["H"] + 2 * k["Hkv"])
    moe = d * k["E"] + len(k["held"]) * 3 * d * k["ff"]
    return 2 * k["V"] * d + k["L"] * (attn + moe + 2 * d) + d


def flops_per_token(cfg: dict) -> float:
    """6 x the parameters a token's forward and backward use: attention,
    router and norms, the held experts at their expected share of the
    top-k (k x held / n_experts = 1 expert a token) and the head (the
    embedding is a lookup); plus 12 x (query width) x the keys each query
    sees: T in a full layer, the window in a windowed one, so the masked
    blocks are not counted as work."""
    k = dims(cfg)
    d = k["d"]
    T = cfg["spec"]["task"]["seq_len"]
    attn = d * k["hd"] * (2 * k["H"] + 2 * k["Hkv"])
    experts = k["K"] * len(k["held"]) / k["E"] * 3 * d * k["ff"]
    active = k["L"] * (attn + d * k["E"] + experts + 2 * d) + d \
        + d * k["V"]
    keys = sum(T if kind == "full_attention"
               else min(cfg["sliding_window"], T) for kind in k["kinds"])
    return 6.0 * active + 12.0 * k["H"] * k["hd"] * keys


def tokens_per_round(cfg: dict) -> int:
    """Every client takes one gradient over its batch each round."""
    t = cfg["spec"]["task"]
    return t["m"] * t["batch_per_client"] * t["seq_len"]


def flops_per_round(cfg: dict) -> float:
    return flops_per_token(cfg) * tokens_per_round(cfg)


# ---------------------------------------------------------------------------
# weights and data
# ---------------------------------------------------------------------------

def _normal(key, shape, scale):
    return (jax.random.normal(key, shape) * scale).astype(jnp.float32)


def init(cfg: dict, seed: int):
    """The program's draw from the seed: three keys (embedding, layers,
    head), one key a layer split into attention and experts."""
    k = dims(cfg)
    d, ff, nh = k["d"], k["ff"], len(k["held"])
    k_emb, k_layers, k_out = jax.random.split(jax.random.PRNGKey(seed), 3)

    def layer(key):
        ka_, km_ = jax.random.split(key, 2)
        ka = jax.random.split(ka_, 4)
        km = jax.random.split(km_, 4)
        return {
            "ln_attn": {"scale": jnp.zeros((d,), jnp.float32)},
            "attn": {"wq": _normal(ka[0], (d, k["H"], k["hd"]), d ** -0.5),
                     "wk": _normal(ka[1], (d, k["Hkv"], k["hd"]), d ** -0.5),
                     "wv": _normal(ka[2], (d, k["Hkv"], k["hd"]), d ** -0.5),
                     # fan-in scaled by its leading axis, as the program's
                     "wo": _normal(ka[3], (k["H"], k["hd"], d),
                                   k["H"] ** -0.5)},
            "ln_mlp": {"scale": jnp.zeros((d,), jnp.float32)},
            "moe": {"router": _normal(km[0], (d, k["E"]), d ** -0.5),
                    "wi": _normal(km[1], (nh, d, ff), d ** -0.5),
                    "wg": _normal(km[2], (nh, d, ff), d ** -0.5),
                    "wo": _normal(km[3], (nh, ff, d), ff ** -0.5)},
        }

    layers = jax.jit(jax.vmap(layer))(jax.random.split(k_layers, k["L"]))
    return {"embed": _normal(k_emb, (k["V"], d), 0.02), "layers": layers,
            "ln_f": {"scale": jnp.zeros((d,), jnp.float32)},
            "unembed": _normal(k_out, (d, k["V"]), d ** -0.5)}


def make_data(cfg: dict, seed: int):
    t = cfg["spec"]["task"]
    batches = make_data_tokens(cfg["vocab_size"], t["m"],
                               t["batch_per_client"], t["seq_len"], seed)
    return batches, init(cfg, seed), float(t["batch_per_client"])


def half_batch(batches: dict) -> dict:
    """Every client's loss over the first half of its tokens: with one
    sequence a client, half of each sequence, not half of the sequences."""
    mask = batches["loss_mask"].copy()
    mask[..., mask.shape[-1] // 2:] = 0.0
    return {**batches, "loss_mask": mask}


# ---------------------------------------------------------------------------
# rope
# ---------------------------------------------------------------------------

def inv_frequencies(head_dim: int, rope: dict) -> np.ndarray:
    """Inverse frequencies of a ``rope_parameters`` section (float64)."""
    theta = rope["rope_theta"]
    pos = theta ** (np.arange(0, head_dim, 2) / head_dim)
    if rope["rope_type"] == "default":
        return 1.0 / pos
    assert rope["rope_type"] == "yarn", rope

    def corr(rot):
        return (head_dim * math.log(rope["original_max_position_embeddings"]
                                    / (rot * 2 * math.pi))
                / (2 * math.log(theta)))

    low = max(math.floor(corr(rope["beta_fast"])), 0)
    high = min(math.ceil(corr(rope["beta_slow"])), head_dim - 1)
    if low == high:
        high += 0.001
    ramp = np.clip((np.arange(head_dim // 2) - low) / (high - low), 0, 1)
    keep = 1.0 - ramp          # share of the original (extrapolated) freq
    return keep / pos + (1.0 - keep) / (rope["factor"] * pos)


def rope_cos_sin(T: int, head_dim: int, rope: dict):
    """(cos, sin), each (T, head_dim), of a ``rope_parameters`` section."""
    inv = jnp.asarray(inv_frequencies(head_dim, rope), jnp.float32)
    ang = jnp.arange(T, dtype=jnp.float32)[:, None] * inv[None, :]
    scale = rope.get("attention_factor", 1.0) \
        if rope["rope_type"] == "yarn" else 1.0
    cos = jnp.concatenate([jnp.cos(ang)] * 2, -1) * scale
    sin = jnp.concatenate([jnp.sin(ang)] * 2, -1) * scale
    return cos, sin


def _rotate(x, cos, sin):
    half = x.shape[-1] // 2
    rot = jnp.concatenate([-x[..., half:], x[..., :half]], -1)
    return x * cos[None, :, None, :] + rot * sin[None, :, None, :]


# ---------------------------------------------------------------------------
# forward and loss
# ---------------------------------------------------------------------------

def matmul(lower: bool):
    """-> ``mm(eq, a, b)``: an einsum at the highest precision, on operands
    rounded to float8 e4m3 first where ``lower`` (the control)."""
    cast = _lm._fp8 if lower else (lambda a: a)

    def mm(eq, a, b):
        return jnp.einsum(eq, cast(a), cast(b), precision=HIGHEST)

    return mm


def attention(q, kk, v, kind: str, window: int, mm):
    """Causal softmax attention of (B, T, H, D) queries over (B, T, Hkv, D)
    keys and values, windowed where ``kind`` is sliding_attention, in
    blocks of Q_BLOCK query rows, each block's scores recomputed in the
    backward pass."""
    B, T, H, D = q.shape
    rep = H // kk.shape[2]
    kk, v = jnp.repeat(kk, rep, axis=2), jnp.repeat(v, rep, axis=2)
    qb = min(Q_BLOCK, T)
    kpos = jnp.arange(T)

    @jax.checkpoint
    def one(args):
        qc, start = args
        qpos = start + jnp.arange(qb)
        ok = kpos[None, :] <= qpos[:, None]
        if kind == "sliding_attention":
            ok &= qpos[:, None] - kpos[None, :] < window
        s = mm("bqhd,bkhd->bhqk", qc, kk) / jnp.sqrt(jnp.float32(D))
        p = jax.nn.softmax(jnp.where(ok, s, -jnp.inf), axis=-1)
        return mm("bhqk,bkhd->bqhd", p, v)

    blocks = jnp.moveaxis(q.reshape(B, T // qb, qb, H, D), 1, 0)
    out = jax.lax.map(one, (blocks, jnp.arange(0, T, qb)))
    return jnp.moveaxis(out, 0, 1).reshape(B, T, H, D)


def experts(h, mp, held, top_k: int, mm):
    """The share of the expert layer that the experts ``held`` give, for
    (B, T, d) tokens: the router scores all its experts, each token keeps
    its top_k, renormalised, and every held expert is computed for every
    token and weighted by its gate (0 where the token did not choose it).
    ``mp`` holds the router and the held experts' weights, in ``held``'s
    order."""
    logits = mm("btd,de->bte", h, mp["router"])
    # the top-k of the logits is the softmax's, without its ties at 0
    _, top_e = jax.lax.top_k(logits, top_k)
    top_p = jnp.take_along_axis(jax.nn.softmax(logits, axis=-1), top_e, -1)
    top_p = top_p / jnp.sum(top_p, axis=-1, keepdims=True)
    gate = jnp.sum(jnp.where(top_e[..., None] == jnp.asarray(held),
                             top_p[..., None], 0.0), axis=-2)   # (B, T, h)

    @jax.checkpoint
    def expert(acc, xs):
        wi, wg, wo, g = xs
        u = jax.nn.silu(mm("btd,df->btf", h, wi)) * mm("btd,df->btf", h, wg)
        return acc + mm("btf,fd->btd", u, wo) * g[..., None], None

    # one expert at a time in a loop, so that the backward pass too holds
    # one expert's activations at a time, each recomputed there
    out, _ = jax.lax.scan(expert, jnp.zeros_like(h),
                          (mp["wi"], mp["wg"], mp["wo"],
                           jnp.moveaxis(gate, -1, 0)))
    return out


def make_block(cfg: dict, lower: bool):
    """-> ``block(x, lp, kind)``: one layer of ``kind`` on (B, T, d)."""
    k = dims(cfg)
    eps, window = cfg["rms_norm_eps"], cfg["sliding_window"]
    mm = matmul(lower)

    def block(x, lp, kind):
        cos, sin = rope_cos_sin(x.shape[1], k["hd"],
                                cfg["rope_parameters"][kind])
        h = _lm._rms(x, lp["ln_attn"]["scale"], eps)
        q = _rotate(mm("btd,dhk->bthk", h, lp["attn"]["wq"]), cos, sin)
        kk = _rotate(mm("btd,dhk->bthk", h, lp["attn"]["wk"]), cos, sin)
        v = mm("btd,dhk->bthk", h, lp["attn"]["wv"])
        o = attention(q, kk, v, kind, window, mm)
        x = x + mm("bthk,hkd->btd", o, lp["attn"]["wo"])
        h = _lm._rms(x, lp["ln_mlp"]["scale"], eps)
        return x + experts(h, lp["moe"], k["held"], k["K"], mm)

    return block


def make_loss(cfg: dict, lower: bool):
    """-> ``loss(params, batch)``: the mean next-token cross-entropy under
    the batch's mask."""
    k = dims(cfg)
    eps = cfg["rms_norm_eps"]
    mm = matmul(lower)
    # each layer's activations are recomputed in the backward pass
    block = jax.checkpoint(make_block(cfg, lower), static_argnums=(2,))

    def loss(params, b):
        x = params["embed"][b["tokens"]]
        for i, kind in enumerate(k["kinds"]):
            x = block(x, jax.tree_util.tree_map(lambda a: a[i],
                                                params["layers"]), kind)
        x = _lm._rms(x, params["ln_f"]["scale"], eps)
        B, T, d = x.shape
        rows = min(LOSS_ROWS, T)

        @jax.checkpoint
        def nll_sum(args):
            xc, tc, mc = args
            logp = jax.nn.log_softmax(mm("btd,dv->btv", xc,
                                         params["unembed"]), axis=-1)
            nll = -jnp.take_along_axis(logp, tc[..., None], -1)[..., 0]
            return jnp.sum(nll * mc)

        # the head's logits a block of rows at a time
        def blocks(a):
            return jnp.moveaxis(a.reshape((B, T // rows, rows) + a.shape[2:]),
                                1, 0)

        mask = b["loss_mask"]
        total = jnp.sum(jax.lax.map(nll_sum, (blocks(x), blocks(b["targets"]),
                                              blocks(mask))))
        return total / jnp.maximum(jnp.sum(mask), 1.0)

    return loss
