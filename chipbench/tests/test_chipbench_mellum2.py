"""The mellum2-12b-a2.5b configuration: its FLOP counts, its reference's
weights against the program's at full size, the cut its file names, and,
at a tiny CPU size (``config`` below), one window through ``run_window``,
the check on a sound program, the planted program faults of
``test_chipbench_faults`` and the reference's control and half-batch
fault against the limits."""
from __future__ import annotations

import pathlib
import sys
import time

import pytest

sys.path.insert(0, str(pathlib.Path(__file__).resolve().parent))

import tiny  # noqa: E402  (puts the benchmark and the program on the path)

import harness  # noqa: E402
import reference  # noqa: E402
from test_chipbench_faults import FAULTS, fresh_program_caches  # noqa: E402,F401

NAME = "mellum2-12b-a2.5b"
FILE = tiny.BENCH / "configs" / f"{NAME}.json"
SEED = 2**31 + 17


def _config() -> dict:
    cfg = harness.load_json(FILE)
    cfg["_file"] = FILE
    return cfg


def config() -> dict:
    """The program's own tiny preset: the same period, rope sections,
    router and held-expert share at width 64, 32 tokens, float32."""
    cfg = _config()
    cfg.update(hidden_size=64, num_attention_heads=4,
               num_key_value_heads=2, head_dim=32,
               moe_intermediate_size=32, vocab_size=128,
               sliding_window=8, num_experts_per_tok=4,
               experts_held=[0, 1, 2, 3])
    cfg["published"] = {**cfg["published"], "num_experts": 16}
    cfg["spec"]["task"].update(reduced=True, seq_len=32)
    return cfg


def test_flop_counts():
    mel = _config()
    task = harness.config_module(FILE)
    # per layer: attention 2304 x 128 x (2 x 16 + 2 x 2), the router's 64
    # outputs, 8 held experts of 3 x 2304 x 896, two norms; untied
    # embedding and head of 12,288 rows; the final norm
    attn, router, expert = 2304 * 128 * 36, 2304 * 64, 3 * 2304 * 896
    assert task.n_params(mel) == 2 * 12288 * 2304 + 4 * (
        attn + router + 8 * expert + 2 * 2304) + 2304 == 297_881_856
    # 6 x (attention, router, norms, the expected 8 x 8 / 64 = 1 held
    # expert a token, the head) plus 12 x (query width 2048) x the keys a
    # query sees: 8192 in the full layer, the 1024 window in the others
    active = 4 * (attn + router + expert + 2 * 2304) + 2304 + 2304 * 12288
    assert task.flops_per_token(mel) == 6 * active + 12 * 2048 * (
        8192 + 3 * 1024) == 853_796_352
    assert task.tokens_per_round(mel) == 2 * 1 * 8192
    assert task.flops_per_round(mel) == 853_796_352 * 16384


def test_reference_params_are_the_program_params_at_full_size():
    import jax

    from repro import configs
    from repro.models import registry
    cfg = _config()
    task = harness.config_module(FILE)
    ref = jax.eval_shape(lambda: task.init(cfg, 0))
    assert sum(int(x.size) for x in jax.tree_util.tree_leaves(ref)) \
        == task.n_params(cfg)
    arch = configs.get_config(cfg["spec"]["task"]["arch"])
    prog = jax.eval_shape(
        lambda: registry.get_model(arch).init(jax.random.PRNGKey(0)))
    assert jax.tree_util.tree_structure(prog) == \
        jax.tree_util.tree_structure(ref)
    assert [x.shape for x in jax.tree_util.tree_leaves(prog)] == \
        [x.shape for x in jax.tree_util.tree_leaves(ref)]


def test_the_configuration_names_its_cut():
    cfg = _config()
    pub = cfg["published"]
    assert (pub["num_hidden_layers"], pub["num_experts"],
            pub["vocab_size"]) == (28, 64, 98304)
    assert (cfg["num_hidden_layers"], cfg["num_experts"],
            cfg["vocab_size"]) == (4, 8, 98304 // 8)
    assert len(cfg["experts_held"]) == cfg["num_experts"]
    # the period runs whole: three windowed layers and a full one
    kinds = cfg["layer_types"][:cfg["num_hidden_layers"]]
    assert kinds == ["sliding_attention"] * 3 + ["full_attention"]
    assert set(cfg["reduced"]) <= set(cfg) and "deployment" in cfg
    manifest = harness.load_json(tiny.ROOT / "BENCHMARK.json")
    entry = {c["name"]: c for c in manifest["configs"]}[NAME]
    assert set(entry["reduced"]) == set(cfg["reduced"])


def test_one_run_call_with_a_whole_number_of_chunks(monkeypatch):
    from repro.spec.build import RunHandle
    calls = []
    real = RunHandle.run

    def counted(self, report=None):
        calls.append(self.spec.engine.rounds)
        return real(self, report)

    monkeypatch.setattr(RunHandle, "run", counted)
    cfg, mix = config(), tiny.mix()
    chunk = cfg["spec"]["engine"]["chunk"]
    run = harness.run_window(cfg, mix, seed=2**31 + 11, seconds=0.5,
                             trace=False, t_start=time.perf_counter())
    # first chunk, timed chunks, then the window: one call
    assert calls[0] == chunk and len(calls) >= 3
    assert all(c == chunk for c in calls[:-1])
    assert calls[-1] == run["rounds"] and run["rounds"] % chunk == 0
    assert run["compiles_in_window"] == 0
    ok, checks = harness.check(cfg, mix, 2**31 + 11, run["prog"])
    assert ok, checks
    ctx = harness.metric_context(
        cfg, run, {run["device"]["kind"]: {"bf16_flops": 1.0}})
    e2e = harness.read_metrics(ctx, [
        {"name": "setup_s", "unit": "s"},
        {"name": "tokens_per_s", "unit": "tokens/s"}])
    assert e2e["setup_s"]["value"] == run["setup_s"]
    assert e2e["tokens_per_s"]["value"] == pytest.approx(
        run["rounds"] * 2 * 32 / run["window_s"])


@pytest.mark.parametrize("fault", sorted(FAULTS))
def test_planted_fault_comes_out_not_correct(fault, monkeypatch,
                                             fresh_program_caches):
    FAULTS[fault](monkeypatch)
    cfg, mix = config(), tiny.mix()
    seed = 2**31 + 21
    _, _, prog = harness.first_chunk(cfg, mix, seed)
    ok, checks = harness.check(cfg, mix, seed, prog)
    assert not ok, checks
    if fault == "state_unchanged":
        assert checks["change_gap"]["value"] == pytest.approx(1.0)


def test_sound_program_comes_out_correct(fresh_program_caches):
    cfg, mix = config(), tiny.mix()
    seed = 2**31 + 22
    _, _, prog = harness.first_chunk(cfg, mix, seed)
    ok, checks = harness.check(cfg, mix, seed, prog)
    assert ok, checks


@pytest.mark.parametrize("fault", [None, "half_batch"])
def test_the_references_control_and_fault_fail_a_limit(fault):
    """The float8 control (``lower``) and half of each client's one
    sequence, the two readings ``control.py`` takes on the chip."""
    cfg, mix = config(), tiny.mix()
    task = harness.config_module(FILE)
    chunk = cfg["spec"]["engine"]["chunk"]
    spec = harness.spec_dict(cfg, mix, SEED, chunk)
    ref_mix = harness.mix_reference(mix)
    good = ref_mix.run_reference(task, cfg, spec, SEED, chunk)
    bad = ref_mix.run_reference(task, cfg, spec, SEED, chunk,
                                lower=fault is None, fault=fault)
    vals = reference.compare(bad, good)
    assert any(vals[k] > v for k, v in cfg["limits"].items()), vals
