"""Dry-run machinery on a small fake mesh (subprocess): lower+compile a
sample of (arch x shape) steps, exercise the artifact writer, the HLO
collective census, and the while-loop trip parser."""
import json
import os
import subprocess
import sys

import pytest

_SCRIPT = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import sys
sys.path.insert(0, os.environ["REPRO_SRC"])
import jax
import jax.numpy as jnp

from repro.launch.steps import build_step, Skip
from repro.launch.dryrun import collective_census, while_loop_info

from repro.launch.mesh import make_mesh
mesh = make_mesh((4, 2), ("data", "model"))

# Use reduced configs via monkeypatching get_config so the small mesh can
# hold them (full configs need the 256-chip mesh).
import repro.configs as configs
real_get = configs.get_config
configs.get_config = configs.get_reduced
try:
    cases = [("smollm-135m", "train_4k"), ("zamba2-1.2b", "decode_32k"),
             ("hubert-xlarge", "prefill_32k"), ("hubert-xlarge",
                                                "decode_32k"),
             ("xlstm-125m", "long_500k")]
    for arch, shape in cases:
        b = build_step(arch, shape, mesh)
        if isinstance(b, Skip):
            print(f"{arch} {shape}: SKIP {b.reason}")
            assert (arch, shape) == ("hubert-xlarge", "decode_32k")
            continue
        compiled = b.lower().compile()
        ma = compiled.memory_analysis()
        hlo = compiled.as_text()
        census = collective_census(hlo)
        trips, parents = while_loop_info(hlo)
        kinds = sorted({c["op"] for c in census})
        print(f"{arch} {shape}: ok peak={ma.temp_size_in_bytes/1e9:.2f}GB "
              f"collectives={kinds} n_while={len(trips)}")
        if shape == "train_4k":
            # the layer scan must be visible with its trip count
            assert any(t == 2 for t in trips.values()), trips
            assert census, "train step must communicate"
finally:
    configs.get_config = real_get
print("DRYRUN-SMALL-OK")
"""


@pytest.mark.slow
def test_dryrun_small_mesh():
    env = dict(os.environ)
    env["REPRO_SRC"] = os.path.join(os.path.dirname(__file__), "..", "src")
    env.pop("XLA_FLAGS", None)
    out = subprocess.run([sys.executable, "-c", _SCRIPT], env=env,
                         capture_output=True, text=True, timeout=1800)
    assert "DRYRUN-SMALL-OK" in out.stdout, (out.stdout[-3000:],
                                             out.stderr[-5000:])


def test_census_parser_units():
    from repro.launch.dryrun import collective_census, _shape_bytes
    assert _shape_bytes("bf16[4,8]{1,0}") == 64
    assert _shape_bytes("f32[]") == 4
    hlo = """
ENTRY %main (p0: f32[16]) -> f32[16] {
  %ag = f32[16]{0} all-gather(%p0), replica_groups={}
  %ar = bf16[8,2]{1,0} all-reduce(%x), to_apply=%add
  ROOT %t = f32[16]{0} copy(%ag)
}
"""
    ops = collective_census(hlo)
    assert {o["op"] for o in ops} == {"all-gather", "all-reduce"}
    assert sum(o["bytes"] for o in ops) == 64 + 32


def test_loop_parser_units():
    from repro.launch.roofline import parse_hlo_loops
    hlo = """
%body.1 (p: s32[]) -> s32[] {
  ROOT %x = s32[] add(%p, %c)
}

%cond.1 (p: s32[]) -> pred[] {
  %c10 = s32[] constant(10)
  ROOT %cmp = pred[] compare(%p, %c10), direction=LT
}

ENTRY %main (a: s32[]) -> s32[] {
  ROOT %w = s32[] while(%a), condition=%cond.1, body=%body.1
}
"""
    trips, parents = parse_hlo_loops(hlo)
    assert trips == {"body.1": 10}
    assert parents == {"body.1": "main"}
