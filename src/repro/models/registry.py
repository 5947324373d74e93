"""Model registry: family -> implementation module.

Every family module exposes:
  init(key, cfg)                      -> params pytree
  apply(params, batch, cfg)           -> logits (B, T, V)
  prefill(params, batch, cfg, max_len)-> (logits, decode_state)
  decode_step(params, state, batch, cfg) -> (logits, decode_state)
  init_decode_state(cfg, batch, seq_len, prefill_len) -> decode_state

``hubert``-style encoder-only archs (attention="bidirectional") have no
decode path; the registry raises for them so callers fail loudly (the
dry-run skips decode shapes for encoder archs, see DESIGN.md §4). Nor do
stacks that mix layer kinds (``layer_types``, Mellum2): they train only.
"""
from __future__ import annotations

from typing import Any, Callable, NamedTuple

import jax.numpy as jnp

from repro.models import dense, moe, ssm, xlstm
from repro.models.config import ArchConfig

_FAMILY_MODULES = {
    "dense": dense,
    "vlm": dense,
    "audio": dense,
    "moe": moe,
    "xlstm": xlstm,
    "hybrid": ssm,
    "ssm": ssm,
}


class Model(NamedTuple):
    cfg: ArchConfig
    init: Callable
    apply: Callable
    prefill: Callable
    decode_step: Callable
    init_decode_state: Callable

    @property
    def has_decode(self) -> bool:
        return (self.cfg.attention != "bidirectional"
                and len(set(self.cfg.layer_types)) <= 1)

    @property
    def is_subquadratic(self) -> bool:
        """True if long-context decode state is bounded (SSM/xLSTM/SWA)."""
        if self.cfg.family in ("xlstm", "hybrid", "ssm"):
            return True
        return self.cfg.sliding_window is not None


def get_model(cfg: ArchConfig) -> Model:
    mod = _FAMILY_MODULES.get(cfg.family)
    if mod is None:
        raise KeyError(f"unknown model family {cfg.family!r}")

    def init(key):
        return mod.init(key, cfg)

    def apply(params, batch):
        return mod.apply(params, batch, cfg)

    def _no_decode(*a, **kw):
        why = (f"encoder-only ({cfg.attention})"
               if cfg.attention == "bidirectional" else
               f"a stack of mixed layer kinds {cfg.layer_types}")
        raise NotImplementedError(f"{cfg.name} is {why}; no decode path")

    if cfg.attention == "bidirectional" or len(set(cfg.layer_types)) > 1:
        pre, dec, ids = _no_decode, _no_decode, _no_decode
    else:
        def pre(params, batch, max_len=None):
            return mod.prefill(params, batch, cfg, max_len=max_len)

        def dec(params, state, batch):
            return mod.decode_step(params, state, batch, cfg)

        def ids(batch_size, seq_len, prefill_len):
            return mod.init_decode_state(cfg, batch_size, seq_len,
                                         prefill_len)

    return Model(cfg=cfg, init=init, apply=apply, prefill=pre,
                 decode_step=dec, init_decode_state=ids)
