"""Mellum2-12B-A2.5B's tiny CPU preset against its plain reference
(``chipbench/configs/mellum2-12b-a2.5b.py``, loaded by path): a period of
three windowed layers and one full layer with YaRN rope, and an expert
layer that holds 4 of 16 experts (top-4), routed dropless."""
import dataclasses
import importlib.util
import math
import pathlib
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import configs
from repro.core.tasks import make_lm_loss
from repro.models import dense, moe, registry
from repro.models.layers import rope
from repro.spec import ExperimentSpec

ROOT = pathlib.Path(__file__).resolve().parents[1]
ARCH = "mellum2-12b-a2.5b"
SEED = 3
T = 32


def _reference():
    spec = importlib.util.spec_from_file_location(
        "mellum2_reference", ROOT / "chipbench" / "configs" / f"{ARCH}.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


REF = _reference()
CFG = configs.get_reduced(ARCH)


def _ref_cfg(arch_cfg=CFG, m=2) -> dict:
    """The reference's configuration dict of an ArchConfig, the published
    rope sections as the benchmark's JSON has them."""
    import json
    pub = json.loads((ROOT / "chipbench" / "configs"
                      / f"{ARCH}.json").read_text())
    return {"num_hidden_layers": arch_cfg.n_layers,
            "hidden_size": arch_cfg.d_model,
            "num_attention_heads": arch_cfg.n_heads,
            "num_key_value_heads": arch_cfg.n_kv_heads,
            "head_dim": arch_cfg.hd, "moe_intermediate_size": arch_cfg.d_ff,
            "vocab_size": arch_cfg.vocab,
            "published": {"num_experts": arch_cfg.n_experts},
            "experts_held": list(arch_cfg.experts_held),
            "num_experts_per_tok": arch_cfg.top_k,
            "layer_types": list(arch_cfg.layer_types),
            "sliding_window": arch_cfg.sliding_window,
            "rope_parameters": pub["rope_parameters"],
            "rms_norm_eps": 1e-6,
            "spec": {"task": {"m": m, "batch_per_client": 1, "seq_len": T}}}


def _batch(vocab, m=2, seed=SEED):
    raw = REF.make_data_tokens(vocab, m, 1, T, seed)
    return jax.tree_util.tree_map(jnp.asarray, raw)


def test_preset_is_the_period_at_a_tiny_size():
    assert CFG.d_model == 64 and CFG.n_heads * CFG.hd == 128
    assert CFG.layer_types == ("sliding_attention",) * 3 + ("full_attention",)
    assert CFG.sliding_window == 8 and CFG.vocab == 128
    assert (CFG.n_experts, len(CFG.experts_held), CFG.top_k) == (16, 4, 4)
    full = configs.get_config(ARCH)
    # the chip's share: every width published; 8 of 64 experts, 16 of 32
    # query heads (2 of 4 key/value heads) and 12,288 of 98,304 rows held
    assert (full.d_model, full.hd, full.d_ff, full.n_experts,
            full.top_k) == (2304, 128, 896, 64, 8)
    assert (full.n_heads, full.n_kv_heads) == (16, 2)
    assert full.experts_held == tuple(range(8)) and full.vocab == 12288
    assert full.n_params() == 297_861_120


def test_program_weights_are_the_reference_draw():
    params = registry.get_model(CFG).init(jax.random.PRNGKey(SEED))
    ref = REF.init(_ref_cfg(), SEED)
    assert jax.tree_util.tree_structure(params) == \
        jax.tree_util.tree_structure(ref)
    for a, b in zip(jax.tree_util.tree_leaves(params),
                    jax.tree_util.tree_leaves(ref)):
        # the same normals times the same fan-in scale, computed as a
        # float32 array in the program and a Python float here
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=1e-6, atol=1e-7)


def test_loss_and_every_leaf_gradient_match_the_reference():
    model = registry.get_model(CFG)
    params = model.init(jax.random.PRNGKey(SEED))
    batch = jax.tree_util.tree_map(lambda a: a[0], _batch(CFG.vocab))
    loss = make_lm_loss(model.apply)
    ref_loss = REF.make_loss(_ref_cfg(), lower=False)
    f_p, g_p = jax.value_and_grad(loss)(params, batch)
    f_r, g_r = jax.value_and_grad(ref_loss)(params, batch)
    # float32 throughout: the program's online-softmax blocks and its
    # sorted expert rows sum in another order than the reference's plain
    # softmax and dense gates, a few float32 ulps on a loss near ln(128)
    assert abs(float(f_p) - float(f_r)) <= 1e-5 * abs(float(f_r))
    for path, a in jax.tree_util.tree_flatten_with_path(g_p)[0]:
        b = g_r
        for key in path:
            b = b[key.key]
        # relative to the leaf's own norm: the same reordered float32 sums,
        # carried through four layers' backward passes
        err = float(jnp.linalg.norm(a - b)) / float(jnp.linalg.norm(b))
        assert err < 1e-4, (jax.tree_util.keystr(path), err)


def _layer_input(n=64, d=64, seed=0):
    return jax.random.normal(jax.random.PRNGKey(seed), (n, d), jnp.float32)


def _uncut_layer(seed=0):
    """One expert layer with all 16 experts' weights."""
    full = dataclasses.replace(CFG, experts_held=tuple(range(16)))
    return full, moe.init_moe_mlp(jax.random.PRNGKey(seed), full)


def test_expert_shares_of_four_chips_add_up_to_the_uncut_layer():
    full, p = _uncut_layer()
    x = _layer_input()
    mm = REF.matmul(lower=False)
    uncut = REF.experts(x[None], p, list(range(16)), CFG.top_k, mm)[0]
    total = jnp.zeros_like(x)
    for chip in range(4):
        held = tuple(range(4 * chip, 4 * chip + 4))
        share = {"router": p["router"],
                 **{w: p[w][4 * chip:4 * chip + 4]
                    for w in ("wi", "wg", "wo")}}
        out, load = moe.held_moe_mlp(
            x, share, dataclasses.replace(CFG, experts_held=held))
        # each chip's share is the reference's share of the same experts
        np.testing.assert_allclose(
            out, REF.experts(x[None], share, list(held), CFG.top_k, mm)[0],
            rtol=1e-5, atol=1e-5)
        total = total + out
    # no expert is shared and the router is computed alike on every chip,
    # so the four shares alone make the layer; float32 sums in another order
    np.testing.assert_allclose(total, uncut, rtol=1e-5, atol=1e-5)
    assert int(load.sum()) > 0


def test_dropless_under_skew():
    _, p = _uncut_layer(seed=1)
    cfg = dataclasses.replace(CFG, experts_held=(0, 1, 2, 3))
    share = {"router": p["router"],
             **{w: p[w][:4] for w in ("wi", "wg", "wo")}}
    # a common direction in every token, and a router column that follows
    # it: expert 2 is in nearly every token's top-4
    u = jax.random.normal(jax.random.PRNGKey(7), (64,))
    x = _layer_input(n=128) + 3.0 * u
    share["router"] = share["router"].at[:, 2].set(0.5 * u)
    out, load = moe.held_moe_mlp(x, share, cfg)
    logits = x @ share["router"]
    _, top_e = jax.lax.top_k(logits, CFG.top_k)
    chosen = np.bincount(np.asarray(top_e).ravel(), minlength=16)[:4]
    # the loads are the router's choices, all of them computed
    np.testing.assert_array_equal(np.asarray(load), chosen)
    assert chosen[2] >= 0.9 * x.shape[0]
    assert chosen[2] > 2 * x.shape[0] * CFG.top_k / CFG.n_experts
    # every token gets all its held experts: the reference computes every
    # held expert for every token, with no capacity
    ref = REF.experts(x[None], share, [0, 1, 2, 3], CFG.top_k,
                      REF.matmul(lower=False))[0]
    np.testing.assert_allclose(out, ref, rtol=1e-5, atol=1e-5)


def test_a_token_past_the_window_moves_only_the_full_layer():
    params = registry.get_model(CFG).init(jax.random.PRNGKey(SEED))
    lp = jax.tree_util.tree_map(lambda a: a[0], params["layers"]["attn"])
    x = _layer_input(n=T, seed=2)[None]
    x2 = x.at[:, 0].add(1.0)                      # the first token moves
    pos = jnp.arange(T)
    far = slice(CFG.sliding_window, None)         # more than a window back
    for kind, moves in (("sliding_attention", False),
                        ("full_attention", True)):
        a, _, _ = dense._attn_full(x, lp, CFG, pos, kind)
        b, _, _ = dense._attn_full(x2, lp, CFG, pos, kind)
        diff = float(jnp.max(jnp.abs(a[:, far] - b[:, far])))
        if moves:
            assert diff > 1e-3, kind
        else:
            # masked scores underflow to exactly 0: no trace of token 0
            assert diff == 0.0, kind
        # within the window the token moves both kinds
        near = float(jnp.max(jnp.abs(a[:, 1:CFG.sliding_window]
                                     - b[:, 1:CFG.sliding_window])))
        assert near > 1e-3, kind


def test_yarn_cos_and_sin_at_the_published_constants():
    full = configs.get_config(ARCH)
    y = full.yarn
    D, theta = full.hd, full.rope_theta
    # the formula, in float64: HF's _compute_yarn_parameters
    dims_ = np.arange(0, D, 2) / D
    pos_freqs = theta ** dims_

    def corr(rot):
        return D * math.log(8192 / (rot * 2 * math.pi)) / (
            2 * math.log(theta))

    low, high = math.floor(corr(32)), math.ceil(corr(1))
    assert (low, high) == (18, 35)
    ramp = np.clip((np.arange(D // 2) - low) / (high - low), 0, 1)
    inv = (1 / (16 * pos_freqs)) * ramp + (1 / pos_freqs) * (1 - ramp)
    positions = np.array([0, 1, 7, 1023, 1024, 4095, 8191])
    ang = positions[:, None] * inv[None, :]
    af = 0.1 * math.log(16) + 1
    assert abs(y.attention_factor - af) < 1e-12
    # rope of the unit vector (1, ..., 1, 0, ..., 0) is (cos, sin)
    unit = jnp.concatenate([jnp.ones(D // 2), jnp.zeros(D // 2)])
    rot = rope(jnp.broadcast_to(unit, (len(positions), 1, D)),
               jnp.asarray(positions), theta, y)[:, 0]
    cos, sin = rot[:, :D // 2], rot[:, D // 2:]
    # float32 angles: pos * inv rounds to 2^-24 of itself, up to 5e-4 rad
    # at position 8191; the scale is exact
    tol = 8191 * 2.0 ** -23 * af
    np.testing.assert_allclose(np.asarray(cos), af * np.cos(ang), atol=tol)
    np.testing.assert_allclose(np.asarray(sin), af * np.sin(ang), atol=tol)
    assert float(cos[0, 0]) == pytest.approx(af, rel=1e-7)
    # and the reference's tables, computed apart from the program's
    rc, rs = REF.rope_cos_sin(8192, D, {"rope_type": "yarn",
                                        "rope_theta": theta, "factor": 16,
                                        "original_max_position_embeddings":
                                        8192, "beta_fast": 32,
                                        "beta_slow": 1,
                                        "attention_factor": af})
    np.testing.assert_allclose(np.asarray(rc)[positions, :D // 2],
                               np.asarray(cos), atol=1e-6)
    np.testing.assert_allclose(np.asarray(rs)[positions, :D // 2],
                               np.asarray(sin), atol=1e-6)


def test_mixed_stacks_have_no_decode_path():
    model = registry.get_model(CFG)
    assert not model.has_decode
    with pytest.raises(NotImplementedError, match="mixed layer kinds"):
        model.prefill({}, {})


def test_preset_trains_two_scan_chunks_through_spec_build_run():
    spec = ExperimentSpec.from_dict({
        "name": "mellum2-preset", "seed": SEED,
        "task": {"kind": "lm", "arch": ARCH, "reduced": True, "m": 2,
                 "batch_per_client": 1, "seq_len": T},
        "algorithm": {"name": "fedepm", "rho": 0.5, "k0": 2, "eps_dp": 0.0,
                      "mu0": 20.0},
        "fleet": {"kind": "synthetic", "latency": "lognormal"},
        "policy": {"name": "sync"},
        "engine": {"name": "scan", "rounds": 4, "chunk": 2}})
    h = spec.build()
    fs = []
    summary = h.run(report=lambda met, f: fs.append(f))
    assert summary["rounds"] == 4 and h.sim.round_idx == 4
    assert math.isfinite(summary["f_final"])
    for leaf in jax.tree_util.tree_leaves(h.sim.state.w_tau):
        assert bool(jnp.all(jnp.isfinite(leaf)))


def test_vmapped_client_gradients_are_each_clients_own():
    """Under the clients' vmap each client's gradient is the one it takes
    alone."""
    model = registry.get_model(CFG)
    params = model.init(jax.random.PRNGKey(SEED))
    batches = _batch(CFG.vocab)
    grad = jax.grad(make_lm_loss(model.apply))
    stacked = jax.jit(jax.vmap(lambda b: grad(params, b)))(batches)
    for i in range(2):
        alone = grad(params, jax.tree_util.tree_map(lambda a: a[i], batches))
        for a, b in zip(jax.tree_util.tree_leaves(stacked),
                        jax.tree_util.tree_leaves(alone)):
            # the same float32 program, jitted or not: round-off only
            np.testing.assert_allclose(a[i], b, rtol=1e-4, atol=1e-6)


def test_the_chunk_names_the_new_stages():
    from repro.sim import lower_rounds
    from repro.telemetry.profiler import DEVICE_SCOPES
    spec = ExperimentSpec.from_dict({
        "name": "mellum2-scopes", "seed": SEED,
        "task": {"kind": "lm", "arch": ARCH, "reduced": True, "m": 2,
                 "batch_per_client": 1, "seq_len": T},
        "algorithm": {"name": "fedepm", "rho": 0.5, "k0": 2, "eps_dp": 0.0,
                      "mu0": 20.0},
        "fleet": {"kind": "synthetic"}, "policy": {"name": "sync"},
        "engine": {"name": "scan", "rounds": 2}})
    text = lower_rounds(spec.build().sim, 2).compile().as_text()
    words = set()
    for path in re.findall(r'op_name="([^"]*)"', text):
        words.update(re.findall(r"[A-Za-z0-9_]+", path))
    assert {"client_grad", "ens", "attention", "attention_window",
            "moe_route", "moe_experts"} <= words & set(DEVICE_SCOPES)
