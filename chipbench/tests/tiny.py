"""Tiny CPU-sized copies of the benchmark's configurations, for tests.

Same files, same code paths, same reference; only the sizes shrink: the
paper task to 400 rows over 8 clients, smollm-135m to the program's own
reduced preset (2 layers, width 96, vocabulary 512, float32).
"""
from __future__ import annotations

import pathlib
import sys

BENCH = pathlib.Path(__file__).resolve().parents[1]
ROOT = BENCH.parent
for p in (str(BENCH), str(ROOT / "src")):
    if p not in sys.path:
        sys.path.insert(0, p)

import harness  # noqa: E402


def config(name: str) -> dict:
    path = BENCH / "configs" / f"{name}.json"
    cfg = harness.load_json(path)
    cfg["_file"] = path
    if name == "paper-logreg":
        cfg["spec"]["task"].update(d=400, m=8)
        cfg["spec"]["engine"]["chunk"] = 4
    else:
        cfg.update(hidden_size=96, intermediate_size=256,
                   num_attention_heads=3, num_key_value_heads=3,
                   num_hidden_layers=2, vocab_size=512)
        cfg["spec"]["task"].update(reduced=True, seq_len=16)
        cfg["spec"]["engine"]["chunk"] = 2
    return cfg


def mix(name: str = "sync") -> dict:
    path = BENCH / "mixes" / f"{name}.json"
    mx = harness.load_json(path)
    mx["_file"] = path
    return mx
