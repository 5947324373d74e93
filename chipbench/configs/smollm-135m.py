"""Plain reference of SmolLM-135M (HuggingFaceTB/SmolLM-135M config.json).

A Llama-architecture decoder: token embedding tied to the output head,
num_hidden_layers pre-norm blocks of RMSNorm -> grouped-query attention
with rotary positions (rotate-half form, theta = rope_theta) -> residual,
RMSNorm -> SwiGLU MLP (silu(x Wi) * (x Wg)) Wo -> residual, a final RMSNorm
and the head. Each RMSNorm weight is kept as (1 + scale) with scale
starting at 0. Written from the published description in float32 at the
highest matmul precision, with plain softmax attention.

The control computes every matmul on operands rounded to float8 e4m3, one
step below the configuration's bfloat16.

Weights: a seeded draw (fan-in scaled normals, embedding normal(0, 0.02)),
see ``init``. Data: synthetic token streams: per client a Zipf(1.1) unigram
of its own topic in which every third token is the sum of the two before
it plus the topic's shift, modulo the vocabulary.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

OBJECTIVE_EVERY_ROUND = False
HIGHEST = jax.lax.Precision.HIGHEST
TOPICS = 8


def dims(cfg: dict) -> dict:
    return {"L": cfg["num_hidden_layers"], "d": cfg["hidden_size"],
            "H": cfg["num_attention_heads"], "Hkv": cfg["num_key_value_heads"],
            "ff": cfg["intermediate_size"], "V": cfg["vocab_size"],
            "hd": cfg["hidden_size"] // cfg["num_attention_heads"]}


def n_params(cfg: dict) -> int:
    """Parameters with the tied embedding counted once."""
    k = dims(cfg)
    d, L = k["d"], k["L"]
    attn = d * k["hd"] * (2 * k["H"] + 2 * k["Hkv"])
    mlp = 3 * d * k["ff"]
    return k["V"] * d + L * (attn + mlp + 2 * d) + d


def flops_per_token(cfg: dict) -> float:
    """6 N for the dense weights (forward and backward) plus 12 L d T for
    the attention scores and values at the cell's sequence length T."""
    k = dims(cfg)
    T = cfg["spec"]["task"]["seq_len"]
    return 6.0 * n_params(cfg) + 12.0 * k["L"] * k["d"] * T


def tokens_per_round(cfg: dict) -> int:
    """Every client takes one gradient over its batch each round."""
    t = cfg["spec"]["task"]
    return t["m"] * t["batch_per_client"] * t["seq_len"]


def flops_per_round(cfg: dict) -> float:
    return flops_per_token(cfg) * tokens_per_round(cfg)


# ---------------------------------------------------------------------------
# weights and data
# ---------------------------------------------------------------------------

def _dense(key, shape):
    return (jax.random.normal(key, shape)
            * (1.0 / jnp.sqrt(shape[0]))).astype(jnp.float32)


def init(cfg: dict, seed: int):
    k = dims(cfg)
    d = k["d"]
    k_emb, k_layers, _ = jax.random.split(jax.random.PRNGKey(seed), 3)

    def layer(key):
        ks = jax.random.split(key, 4)
        ka = jax.random.split(ks[0], 4)
        km = jax.random.split(ks[1], 3)
        return {
            "ln_attn": {"scale": jnp.zeros((d,), jnp.float32)},
            "attn": {"wq": _dense(ka[0], (d, k["H"], k["hd"])),
                     "wk": _dense(ka[1], (d, k["Hkv"], k["hd"])),
                     "wv": _dense(ka[2], (d, k["Hkv"], k["hd"])),
                     "wo": _dense(ka[3], (k["H"], k["hd"], d))},
            "mlp": {"wi": _dense(km[0], (d, k["ff"])),
                    "wg": _dense(km[1], (d, k["ff"])),
                    "wo": _dense(km[2], (k["ff"], d))},
            "ln_mlp": {"scale": jnp.zeros((d,), jnp.float32)},
        }

    layers = jax.jit(jax.vmap(layer))(jax.random.split(k_layers, k["L"]))
    embed = (jax.random.normal(k_emb, (k["V"], d)) * 0.02).astype(jnp.float32)
    return {"embed": embed, "layers": layers,
            "ln_f": {"scale": jnp.zeros((d,), jnp.float32)}}


def token_batches(vocab: int, m: int, per_client: int, seq: int, seed: int):
    rng = np.random.default_rng(seed)
    base = 1.0 / np.arange(1, vocab + 1) ** 1.1
    probs = []
    for _ in range(TOPICS):
        p = base[rng.permutation(vocab)]
        probs.append(p / p.sum())
    shift = rng.integers(1, vocab, size=TOPICS)
    draw = np.random.default_rng(seed + 1)
    toks = np.empty((m, per_client, seq + 1), np.int32)
    for i in range(m):
        t = i % TOPICS
        for b in range(per_client):
            row = draw.choice(vocab, size=seq + 1, p=probs[t])
            for j in range(2, seq + 1, 3):
                row[j] = (row[j - 1] + row[j - 2] + shift[t]) % vocab
            toks[i, b] = row
    return {"tokens": toks[:, :, :-1], "targets": toks[:, :, 1:].copy(),
            "loss_mask": np.ones((m, per_client, seq), np.float32)}


def make_data(cfg: dict, seed: int):
    t = cfg["spec"]["task"]
    batches = token_batches(cfg["vocab_size"], t["m"], t["batch_per_client"],
                            t["seq_len"], seed)
    return batches, init(cfg, seed), float(t["batch_per_client"])


def half_batch(batches: dict) -> dict:
    """Every client's second half of sequences left out of its mean."""
    mask = batches["loss_mask"].copy()
    mask[:, mask.shape[1] // 2:] = 0.0
    return {**batches, "loss_mask": mask}


def state_dtype(lower: bool):
    return jnp.float32


# ---------------------------------------------------------------------------
# forward and loss
# ---------------------------------------------------------------------------

def _fp8(a):
    return a.astype(jnp.float8_e4m3fn).astype(jnp.float32)


def _rms(x, scale, eps):
    var = jnp.mean(jnp.square(x), axis=-1, keepdims=True)
    return x * jax.lax.rsqrt(var + eps) * (1.0 + scale)


def _rope(x, theta):
    T, D = x.shape[1], x.shape[-1]
    half = D // 2
    inv = theta ** (-jnp.arange(0, half, dtype=jnp.float32) / half)
    ang = jnp.arange(T, dtype=jnp.float32)[:, None] * inv[None, :]
    cos = jnp.concatenate([jnp.cos(ang)] * 2, -1)[None, :, None, :]
    sin = jnp.concatenate([jnp.sin(ang)] * 2, -1)[None, :, None, :]
    rot = jnp.concatenate([-x[..., half:], x[..., :half]], -1)
    return x * cos + rot * sin


def make_loss(cfg: dict, lower: bool):
    k = dims(cfg)
    eps, theta = cfg["rms_norm_eps"], cfg["rope_theta"]
    cast = _fp8 if lower else (lambda a: a)

    def mm(eq, a, b):
        return jnp.einsum(eq, cast(a), cast(b), precision=HIGHEST)

    def block(x, lp):
        T = x.shape[1]
        h = _rms(x, lp["ln_attn"]["scale"], eps)
        q = _rope(mm("btd,dhk->bthk", h, lp["attn"]["wq"]), theta)
        kk = _rope(mm("btd,dhk->bthk", h, lp["attn"]["wk"]), theta)
        v = mm("btd,dhk->bthk", h, lp["attn"]["wv"])
        rep = k["H"] // k["Hkv"]
        kk, v = jnp.repeat(kk, rep, axis=2), jnp.repeat(v, rep, axis=2)
        s = mm("bqhd,bkhd->bhqk", q, kk) / jnp.sqrt(jnp.float32(k["hd"]))
        causal = jnp.tril(jnp.ones((T, T), bool))
        p = jax.nn.softmax(jnp.where(causal, s, -jnp.inf), axis=-1)
        o = mm("bhqk,bkhd->bqhd", p, v)
        x = x + mm("bthk,hkd->btd", o, lp["attn"]["wo"])
        h = _rms(x, lp["ln_mlp"]["scale"], eps)
        u = jax.nn.silu(mm("btd,df->btf", h, lp["mlp"]["wi"])) \
            * mm("btd,df->btf", h, lp["mlp"]["wg"])
        return x + mm("btf,fd->btd", u, lp["mlp"]["wo"]), None

    def loss(params, b):
        x = params["embed"][b["tokens"]]
        # each block's activations are recomputed in the backward pass, so
        # the full context's attention fits beside the clients' state
        x, _ = jax.lax.scan(jax.checkpoint(block), x, params["layers"])
        x = _rms(x, params["ln_f"]["scale"], eps)
        logits = mm("btd,vd->btv", x, params["embed"])
        logp = jax.nn.log_softmax(logits, axis=-1)
        nll = -jnp.take_along_axis(logp, b["targets"][..., None], -1)[..., 0]
        mask = b["loss_mask"]
        return jnp.sum(nll * mask) / jnp.maximum(jnp.sum(mask), 1.0)

    return loss
