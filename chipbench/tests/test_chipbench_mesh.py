"""A cell whose client axis spans four chips, on four virtual CPU devices.

The reference spread over four chips reads what it reads on one; the
program at ``engine.mesh`` 4 comes out correct against it; each fault the
timed path can have, planted in the program underneath a whole first
chunk, comes out not correct, as does the lower-precision control. All
of it runs in one subprocess, so the forced device count never reaches
the other tests' one-device view.
"""
from __future__ import annotations

import json
import os
import pathlib
import subprocess
import sys

import pytest

TESTS = pathlib.Path(__file__).resolve().parent
ROOT = TESTS.parents[1]
CONFIG = "smollm-135m-m8"
FAULTS = ("state_unchanged", "half_batch", "no_exchange",
          "altered_aggregate")

_SCRIPT = r"""
import json
import sys

sys.path[:0] = sys.argv[1:]
import jax
import pytest

import harness
import reference
import tiny
from test_chipbench_faults import FAULTS

assert len(jax.devices()) == 4, jax.devices()
cfg, mix = tiny.config(CONFIG), tiny.mix()
task = harness.config_module(cfg["_file"])
chunk = cfg["spec"]["engine"]["chunk"]
mix_ref = harness.mix_reference(mix)
seed = 2**31 + 41
spec = harness.spec_dict(cfg, mix, seed, chunk)
out = {"chips": harness.mesh_chips(cfg)}

# the same rounds on one chip: the configuration without its mesh
one = {**spec, "engine": {k: v for k, v in spec["engine"].items()
                          if k != "mesh"}}
ref4 = mix_ref.run_reference(task, cfg, spec, seed, chunk, chips=4)
ref1 = mix_ref.run_reference(task, cfg, one, seed, chunk, chips=1)
out["four_vs_one"] = reference.compare(ref4, ref1)
ctl = mix_ref.run_reference(task, cfg, spec, seed, chunk, chips=4,
                            lower=True)
out["control"] = reference.compare(ctl, ref4)


def no_exchange(mp):
    # the exchange between chips left out of the aggregate: the program
    # aggregates the clients of chip 0 alone
    from repro.kernels.ens import ops
    real = ops.ens_tree

    def local(Z, lam, eta, impl="ref"):
        return real(jax.tree_util.tree_map(lambda z: z[:z.shape[0] // 4], Z),
                    lam, eta, impl=impl)

    mp.setattr(ops, "ens_tree", local)


FAULTS = {**FAULTS, "no_exchange": no_exchange}
for name in (None,) + tuple(sorted(FAULTS)):
    harness.drop_program_caches()
    with pytest.MonkeyPatch.context() as mp:
        if name:
            FAULTS[name](mp)
        _, _, prog = harness.first_chunk(cfg, mix, seed)
    ok, checks = harness.check(cfg, mix, seed, prog, chips=4)
    out[name or "sound"] = {"ok": ok,
                            **{k: c["value"] for k, c in checks.items()}}
print("RESULT " + json.dumps(out))
"""


@pytest.fixture(scope="module")
def readings():
    env = {**os.environ, "JAX_PLATFORMS": "cpu",
           "XLA_FLAGS": "--xla_force_host_platform_device_count=4"}
    script = _SCRIPT.replace("CONFIG", repr(CONFIG))
    p = subprocess.run(
        [sys.executable, "-c", script, str(TESTS), str(TESTS.parent),
         str(ROOT / "src")],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=900)
    assert p.returncode == 0, p.stderr[-4000:]
    line, = [ln for ln in p.stdout.splitlines() if ln.startswith("RESULT ")]
    return json.loads(line[len("RESULT "):])


def test_the_configuration_spans_four_chips(readings):
    assert readings["chips"] == 4


def test_four_chips_read_what_one_reads(readings):
    vals = readings["four_vs_one"]
    # the same arithmetic, summed in another order across chips: float32
    # rounding, which a leaf's change (a small difference of parameters
    # of order 1) reads at about an ulp of the parameter over the change
    assert vals["loss_gap"] < 1e-6
    assert vals["grad_gap"] < 1e-6
    assert vals["change_gap"] < 1e-3
    assert vals["sim_mismatch"] == 0


def test_the_program_on_four_chips_comes_out_correct(readings):
    assert readings["sound"]["ok"], readings["sound"]


@pytest.mark.parametrize("fault", FAULTS)
def test_planted_fault_on_four_chips_comes_out_not_correct(readings, fault):
    assert not readings[fault]["ok"], readings[fault]
    if fault == "state_unchanged":
        assert readings[fault]["change_gap"] == pytest.approx(1.0)


def test_the_control_on_four_chips_comes_out_not_correct(readings):
    cfg = json.loads((TESTS.parent / "configs" / f"{CONFIG}.json")
                     .read_text())
    vals = readings["control"]
    assert any(v > cfg["limits"][k] for k, v in vals.items()), vals
