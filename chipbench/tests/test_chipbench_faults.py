"""The comparison that decides ``correct`` rejects a broken timed path and
the lower-precision control, at a tiny CPU size.

Each fault is planted in the program underneath a whole first chunk
through ``run()``, as a benchmark run takes it; the check must then come
out false. The cells run on one chip, so there is no exchange between
chips to leave out.
"""
from __future__ import annotations

import pathlib
import sys

import pytest

sys.path.insert(0, str(pathlib.Path(__file__).resolve().parent))

import tiny  # noqa: E402

import harness  # noqa: E402
import reference  # noqa: E402

CELLS = ["paper-logreg", "smollm-135m"]


@pytest.fixture
def fresh_program_caches(monkeypatch):
    """Programs and task data built anew, so a planted fault is traced."""
    import importlib
    for mod, name in harness.PROGRAM_CACHES:
        monkeypatch.setattr(importlib.import_module(mod), name, {})


def frozen_state(monkeypatch):
    from repro.core import fedepm
    real = fedepm.scan_round

    def frozen(state, xs, batches, loss_fn, cfg):
        return state, real(state, xs, batches, loss_fn, cfg)[1]

    monkeypatch.setattr(fedepm, "scan_round", frozen)


def half_batch(monkeypatch):
    import jax.numpy as jnp

    from repro.core import tasks
    for attr, key, axis in (("make_logistic_loss", "mask", -1),
                            ("make_lm_loss", "loss_mask", 0)):
        real = getattr(tasks, attr)

        def make(*a, _real=real, _key=key, _axis=axis, **k):
            f = _real(*a, **k)

            def loss(w, b):
                m = b[_key]
                n = m.shape[_axis]
                keep = (jnp.arange(n) < n // 2).reshape(
                    (-1,) + (1,) * (m.ndim - 1) if _axis == 0 else (n,))
                return f(w, {**b, _key: m * keep})
            return loss

        monkeypatch.setattr(tasks, attr, make)


def altered_aggregate(monkeypatch):
    import jax

    from repro.kernels.ens import ops
    real = ops.ens_tree

    def altered(Z, lam, eta, impl="ref"):
        return jax.tree_util.tree_map(lambda x: x + 1e-3,
                                      real(Z, lam, eta, impl=impl))

    monkeypatch.setattr(ops, "ens_tree", altered)


FAULTS = {"state_unchanged": frozen_state, "half_batch": half_batch,
          "altered_aggregate": altered_aggregate}


@pytest.mark.parametrize("fault", sorted(FAULTS))
@pytest.mark.parametrize("name", CELLS)
def test_planted_fault_comes_out_not_correct(name, fault, monkeypatch,
                                             fresh_program_caches):
    FAULTS[fault](monkeypatch)
    cfg, mix = tiny.config(name), tiny.mix()
    seed = 2**31 + 21
    _, _, prog = harness.first_chunk(cfg, mix, seed)
    ok, checks = harness.check(cfg, mix, seed, prog)
    assert not ok, checks
    if fault == "state_unchanged":
        assert checks["change_gap"]["value"] == pytest.approx(1.0)


@pytest.mark.parametrize("name", CELLS)
def test_sound_program_comes_out_correct(name, fresh_program_caches):
    cfg, mix = tiny.config(name), tiny.mix()
    seed = 2**31 + 22
    _, _, prog = harness.first_chunk(cfg, mix, seed)
    ok, checks = harness.check(cfg, mix, seed, prog)
    assert ok, checks


@pytest.mark.parametrize("name", CELLS)
def test_lower_precision_control_comes_out_not_correct(name):
    cfg, mix = tiny.config(name), tiny.mix()
    seed = 2**31 + 23
    task = harness.config_module(cfg["_file"])
    chunk = cfg["spec"]["engine"]["chunk"]
    spec = harness.spec_dict(cfg, mix, seed, chunk)
    mix_ref = harness.mix_reference(mix)
    ref = mix_ref.run_reference(task, cfg, spec, seed, chunk)
    ctl = mix_ref.run_reference(task, cfg, spec, seed, chunk, lower=True)
    vals = reference.compare(ctl, ref)
    over = [k for k, v in vals.items() if v > cfg["limits"][k]]
    assert over, vals
