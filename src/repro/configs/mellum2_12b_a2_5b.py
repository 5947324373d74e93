"""mellum2-12b-a2.5b [moe]: one chip's share of Mellum2-12B-A2.5B
(JetBrains/Mellum2-12B-A2.5B-Instruct config.json): d_model=2304, 32H
(GQA kv=4) at head_dim 128, of which a chip holds 16H (kv=2), a period
of 3 sliding-window layers (window 1024, rope theta 5e5) and 1 full
layer (YaRN rope: factor 16 over 8192 positions, beta 32/1, attention
factor 1.2773), a sparse MoE in every layer (64 experts, top-8, width
896, renormalised top-k gates, no shared expert), untied vocabulary,
RMSNorm eps 1e-6.

Deployment: each layer of a silo is split over 8 chips by expert
parallelism, 8 of the 64 experts on each, and the vocabulary over the
same 8; attention is split two ways by heads, each chip holding 16 of
the 32 query heads and 2 of the 4 key/value heads (a pair of chips
would add their attention outputs); the layers past the first period lie
on further chips, as stages of a pipeline. This config is what one of
those 8 chips holds of the first period: ``CONFIG`` keeps every width
(head_dim 128, the router's 64 outputs, top-8) and holds experts 0-7,
query heads 0-15 with their key/value heads 0-1, and the first eighth of
the vocabulary. What the other chips' experts and heads would add is not
computed, and nothing stands in for them. (Whole attention on every chip
does not fit: two silos' round then needs 15.83 GiB of the chip's 15.75,
described-chip compile.)

Assumed, as the published config names none of it: softmax router
scores (the convention of its ``norm_topk_prob`` key); no q/k norm and no
attention bias; the "MTP head" of the model's description (unconfirmed,
no config key) is left out, so training uses the main head only.
"""
from __future__ import annotations

import dataclasses

import jax.numpy as jnp

from repro.models.config import ArchConfig, Yarn

SOURCE = ("https://huggingface.co/JetBrains/Mellum2-12B-A2.5B-Instruct/"
          "blob/main/config.json")
# the published counts that CONFIG holds a share of
PUBLISHED = {"n_layers": 28, "n_experts": 64, "vocab": 98304,
             "n_heads": 32, "n_kv_heads": 4}
CHIPS_PER_LAYER = 8

CONFIG = ArchConfig(
    name="mellum2-12b-a2.5b",
    family="moe",
    n_layers=4,                       # one period of 28
    d_model=2304,
    n_heads=32 // 2,                  # 16 of 32 query heads
    n_kv_heads=4 // 2,                # 2 of 4 key/value heads
    head_dim=128,
    d_ff=896,                         # moe_intermediate_size
    vocab=98304 // CHIPS_PER_LAYER,   # 12,288 of 98,304
    norm="rmsnorm",
    mlp="swiglu",
    bias=False,
    rope_theta=500000.0,
    attention="causal",
    sliding_window=1024,
    layer_types=("sliding_attention",) * 3 + ("full_attention",),
    yarn=Yarn(factor=16.0, original_max_position=8192, beta_fast=32.0,
              beta_slow=1.0, attention_factor=1.2772588722239782),
    n_experts=64,
    top_k=8,
    experts_held=tuple(range(64 // CHIPS_PER_LAYER)),   # 8 of 64
    tie_embeddings=False,
    dtype=jnp.bfloat16,
    param_dtype=jnp.float32,
    source=SOURCE,
)

FED_PLAN = {"mode": "spatial", "m": 2}


def reduced() -> ArchConfig:
    """The CPU preset: the same period and mechanisms at a tiny size
    (query width 128 over d_model 64, window 8, 4 of 16 experts held,
    top-4)."""
    return dataclasses.replace(
        CONFIG, d_model=64, n_heads=4, n_kv_heads=2, head_dim=32, d_ff=32,
        vocab=128, sliding_window=8, n_experts=16, top_k=4,
        experts_held=(0, 1, 2, 3), dtype=jnp.float32)
