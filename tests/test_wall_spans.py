"""The program's names in a profiler trace (repro.telemetry.profiler):
device stages as ``jax.named_scope`` in the compiled chunk's op metadata,
host phases as ``TraceAnnotation`` spans, and ``FedSim.host_syncs``
counting every device-to-host read of ``RunHandle.run``."""
import pathlib
import re

import pytest

from repro.sim import lower_rounds
from repro.spec import ExperimentSpec
from repro.telemetry.profiler import DEVICE_SCOPES, HOST_SPANS

SRC = pathlib.Path(__file__).resolve().parents[1] / "src" / "repro"
WORD = re.compile(r"[A-Za-z0-9_]+")


def _spec(engine: str, rounds: int, *, chunk: int | None = None,
          **sections) -> ExperimentSpec:
    d = {"name": "wall-spans", "seed": 3,
         "task": {"kind": "logreg", "d": 400, "n": 14, "m": 8},
         "algorithm": {"name": "fedepm", "rho": 0.5, "k0": 2,
                       "eps_dp": 0.1},
         "fleet": {"kind": "synthetic", "seed": 5},
         "policy": {"name": "sync"},
         "engine": {"name": engine, "rounds": rounds}}
    if chunk is not None:
        d["engine"]["chunk"] = chunk
    for key, val in sections.items():
        d[key] = {**d.get(key, {}), **val}
    return ExperimentSpec.from_dict(d)


def _lm(engine: str, rounds: int, chunk: int | None = None):
    return _spec(engine, rounds, chunk=chunk,
                 task={"kind": "lm", "arch": "smollm-135m", "reduced": True,
                       "m": 2, "batch_per_client": 1, "seq_len": 16},
                 algorithm={"eps_dp": 0.0, "mu0": 20.0})


def _scopes_in(hlo_text: str) -> set:
    words = set()
    for path in re.findall(r'op_name="([^"]*)"', hlo_text):
        words.update(WORD.findall(path))
    return words & set(DEVICE_SCOPES)


def test_names_in_the_source_are_the_listed_names():
    scopes, spans = set(), set()
    for f in SRC.rglob("*.py"):
        text = f.read_text()
        scopes.update(re.findall(r'named_scope\("([^"]+)"\)', text))
        if f.name != "profiler.py":
            spans.update(re.findall(r'"(repro\.[a-z_]+\.[a-z_]+)"', text))
    assert scopes == set(DEVICE_SCOPES)
    assert spans == set(HOST_SPANS)
    assert len(DEVICE_SCOPES + HOST_SPANS) == len(scopes) + len(spans)


def test_the_logreg_chunk_names_its_stages():
    h = _spec("scan", 2).build()
    text = lower_rounds(h.sim, 2).compile().as_text()
    assert {"ens", "client_grad", "client_prox", "dp_noise"} <= \
        _scopes_in(text)


def test_the_lm_chunk_names_attention_forward_and_backward():
    h = _lm("scan", 1).build()
    text = lower_rounds(h.sim, 1).compile().as_text()
    assert {"ens", "client_grad", "client_prox", "attention"} <= \
        _scopes_in(text)
    paths = re.findall(r'op_name="([^"]*attention[^"]*)"', text)
    assert any("transpose" in p and "client_grad" in p for p in paths)


def test_the_upload_chain_names_its_round_trips():
    h = _spec("scan", 1, codec={"topk_frac": 0.5, "bits": 8,
                                "error_feedback": True}).build()
    assert "upload_ef" in _scopes_in(
        lower_rounds(h.sim, 1).compile().as_text())
    h = _spec("scan", 1, codec={"topk_frac": 0.5, "bits": 8}).build()
    assert "upload_codec" in _scopes_in(
        lower_rounds(h.sim, 1).compile().as_text())


@pytest.mark.parametrize("engine", ["eager", "scan"])
def test_host_syncs_count_every_read_of_a_run(engine):
    """Logreg, R rounds: each eager step reads its candidates and its
    policy mask; each scan chunk reads its candidate stream (one pass
    without abandoned rounds) and its broadcast stream; both read the
    objective once a round and the accuracy once."""
    rounds, chunk = 6, 3
    h = _spec(engine, rounds,
              chunk=chunk if engine == "scan" else None).build()
    before = h.sim.host_syncs
    summary = h.run()
    assert summary["abandoned_rounds"] == 0
    per_round = 2 if engine == "eager" else 0
    per_chunk = 0 if engine == "eager" else 2
    assert h.sim.host_syncs - before == (
        rounds * (per_round + 1) + (rounds // chunk) * per_chunk + 1)


def test_host_syncs_count_the_lm_summary_read():
    """An LM pytree has no per-round objective on the scan path: the
    summary reads f once, after one candidate stream per chunk."""
    h = _lm("scan", 2, chunk=1).build()
    before = h.sim.host_syncs
    h.run()
    assert h.sim.host_syncs - before == 2 + 1
