"""Per-architecture smoke tests (deliverable f): for each of the ten
assigned archs, instantiate the REDUCED variant, run one forward and one
FedEPM train round on CPU, assert output shapes + finiteness; plus decode
parity (prefill + step-by-step decode == full forward) per family."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import configs
from repro.core import fedepm
from repro.core.tasks import make_chunked_lm_loss, make_lm_loss
from repro.models import dense as dense_mod
from repro.models import registry

ARCHS = configs.ALL_ARCHS


def _batch_for(cfg, B, T, key, lead=()):
    b = {}
    shape = lead + (B, T)
    if cfg.family == "audio":
        b["frame_embeds"] = jax.random.normal(key, shape + (cfg.d_model,))
        t_total = T
    elif cfg.family == "vlm":
        b["tokens"] = jax.random.randint(key, shape, 0, cfg.vocab)
        b["patch_embeds"] = jax.random.normal(
            jax.random.fold_in(key, 1), lead + (B, cfg.n_patches,
                                                cfg.d_model))
        t_total = T + cfg.n_patches
    else:
        b["tokens"] = jax.random.randint(key, shape, 0, cfg.vocab)
        t_total = T
    b["targets"] = jax.random.randint(jax.random.fold_in(key, 2),
                                      lead + (B, t_total), 0, cfg.vocab)
    b["loss_mask"] = jnp.ones(lead + (B, t_total), jnp.float32)
    return b, t_total


@pytest.mark.parametrize("arch", ARCHS)
def test_smoke_forward(arch):
    cfg = configs.get_reduced(arch)
    # experts whose weights the preset holds (all of them but for a
    # held-expert share, whose router may score more)
    assert cfg.n_layers <= 4 and cfg.d_model <= 512 and \
        cfg.n_local_experts <= 4
    model = registry.get_model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    B, T = 2, 32
    batch, t_total = _batch_for(cfg, B, T, jax.random.PRNGKey(1))
    logits = model.apply(params, batch)
    assert logits.shape == (B, t_total, cfg.vocab)
    assert bool(jnp.all(jnp.isfinite(logits)))


@pytest.mark.parametrize("arch", ARCHS)
def test_smoke_fedepm_train_round(arch):
    """One FedEPM round over the reduced arch: the paper's technique as
    the trainer for every assigned architecture."""
    cfg = configs.get_reduced(arch)
    model = registry.get_model(cfg)
    m, B, T = 4, 2, 16
    loss = make_lm_loss(model.apply)
    fcfg = fedepm.FedEPMConfig.paper_defaults(m=m, rho=0.5, k0=2,
                                              eps_dp=0.1)
    params0 = model.init(jax.random.PRNGKey(0))
    state = fedepm.init_state(jax.random.PRNGKey(1), params0, fcfg)
    batch, _ = _batch_for(cfg, B, T, jax.random.PRNGKey(2), lead=(m,))
    new_state, metrics = jax.jit(
        lambda s, b: fedepm.fedepm_round(s, b, loss, fcfg))(state, batch)
    for leaf in jax.tree_util.tree_leaves(new_state.W):
        assert bool(jnp.all(jnp.isfinite(leaf.astype(jnp.float32))))
    assert int(metrics.selected.sum()) == 2
    assert bool(jnp.isfinite(metrics.snr))


@pytest.mark.parametrize("arch", ARCHS)
def test_decode_parity(arch):
    """prefill(T-4) + 4 decode steps == full forward at those positions."""
    cfg = configs.get_reduced(arch)
    if cfg.family == "moe":
        # tight capacity drops tokens in the full forward but not in
        # 1-token decode -- use drop-free capacity for exact parity
        cfg = dataclasses.replace(cfg, capacity_factor=8.0)
    model = registry.get_model(cfg)
    if not model.has_decode:
        pytest.skip("encoder-only: no decode path (documented skip)")
    if cfg.family == "vlm":
        pytest.skip("vlm decode parity covered via dense family")
    params = model.init(jax.random.PRNGKey(0))
    B, T = 2, 21
    toks = jax.random.randint(jax.random.PRNGKey(1), (B, T), 0, cfg.vocab)
    full = model.apply(params, {"tokens": toks})
    Tp = T - 4
    lg, st = model.prefill(params, {"tokens": toks[:, :Tp]},
                           max_len=T + 4)
    np.testing.assert_allclose(
        np.asarray(lg[:, 0], np.float32), np.asarray(full[:, Tp - 1],
                                                     np.float32),
        atol=2e-2, rtol=2e-2)
    for t in range(Tp, T):
        lg, st = model.decode_step(params, st, {"tokens": toks[:, t:t + 1]})
        np.testing.assert_allclose(
            np.asarray(lg[:, 0], np.float32),
            np.asarray(full[:, t], np.float32), atol=2e-2, rtol=2e-2)


def test_chunked_loss_matches_full():
    cfg = configs.get_reduced("smollm-135m")
    model = registry.get_model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    B, T = 2, 40
    batch, _ = _batch_for(cfg, B, T, jax.random.PRNGKey(1))
    full = make_lm_loss(model.apply)(params, batch)
    from repro.models.registry import _FAMILY_MODULES
    mod = _FAMILY_MODULES[cfg.family]
    hidden = lambda p, b: mod.hidden(p, b, cfg)  # noqa: E731
    unembed = lambda h, p: dense_mod.unembed(h, p, cfg)  # noqa: E731
    chunked = make_chunked_lm_loss(hidden, unembed, chunk=16)(params, batch)
    np.testing.assert_allclose(float(chunked), float(full), rtol=1e-5)


def test_vlm_patch_prefix_changes_logits():
    cfg = configs.get_reduced("llava-next-34b")
    model = registry.get_model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    B, T = 2, 12
    toks = jax.random.randint(jax.random.PRNGKey(1), (B, T), 0, cfg.vocab)
    pe1 = jax.random.normal(jax.random.PRNGKey(2),
                            (B, cfg.n_patches, cfg.d_model))
    out1 = model.apply(params, {"tokens": toks, "patch_embeds": pe1})
    out2 = model.apply(params, {"tokens": toks, "patch_embeds": pe1 * 2.0})
    assert out1.shape[1] == T + cfg.n_patches
    # text logits attend to patches, so they must differ
    assert float(jnp.max(jnp.abs(out1[:, -1] - out2[:, -1]))) > 1e-4


def test_moe_routing_properties():
    cfg = configs.get_reduced("mixtral-8x7b")
    cfg = dataclasses.replace(cfg, capacity_factor=8.0)  # effectively dropless
    from repro.models import moe
    params = moe.init(jax.random.PRNGKey(0), cfg)
    x = jax.random.normal(jax.random.PRNGKey(1), (2, 8, cfg.d_model))
    out, aux = moe.moe_mlp(x, jax.tree_util.tree_map(lambda p: p[0],
                                                     params["layers"])["moe"],
                           cfg)
    assert out.shape == x.shape
    assert float(aux["dropped"]) == 0.0
    assert float(aux["lb_loss"]) > 0.0


def test_xlstm_chunk_invariance():
    cfg = configs.get_reduced("xlstm-125m")
    model = registry.get_model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    toks = jax.random.randint(jax.random.PRNGKey(1), (2, 19), 0, cfg.vocab)
    o1 = model.apply(params, {"tokens": toks})
    cfg2 = dataclasses.replace(cfg, ssm_chunk=4)
    o2 = registry.get_model(cfg2).apply(params, {"tokens": toks})
    np.testing.assert_allclose(np.asarray(o1), np.asarray(o2), atol=2e-5)


def test_hubert_bidirectional():
    """Encoder attends to future frames: flipping a LATE frame changes
    EARLY outputs."""
    cfg = configs.get_reduced("hubert-xlarge")
    model = registry.get_model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    B, T = 1, 16
    fr = jax.random.normal(jax.random.PRNGKey(1), (B, T, cfg.d_model))
    out1 = model.apply(params, {"frame_embeds": fr})
    fr2 = fr.at[:, -1].multiply(3.0)
    out2 = model.apply(params, {"frame_embeds": fr2})
    assert float(jnp.max(jnp.abs(out1[:, 0] - out2[:, 0]))) > 1e-5
