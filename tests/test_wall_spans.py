"""The program's names in a profiler trace (repro.telemetry.profiler):
device stages as ``jax.named_scope`` in the compiled chunk's op metadata,
host phases as ``TraceAnnotation`` spans, ``FedSim.host_syncs``
counting every device-to-host read of ``RunHandle.run``, and the scan
path's one batched objective read a chunk."""
import pathlib
import re
import sys

import jax.numpy as jnp
import numpy as np
import pytest

from repro.sim import lower_rounds, run_rounds
from repro.spec import ExperimentSpec
from repro.spec.build import RunHandle
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
    """Logreg, R rounds: each eager step reads its candidates, its policy
    mask and the round's objective; each scan chunk reads its candidate
    stream (one pass without abandoned rounds), its broadcast stream and
    its rounds' objectives (one batched read); both read the accuracy
    once."""
    rounds, chunk = 6, 3
    h = _spec(engine, rounds,
              chunk=chunk if engine == "scan" else None).build()
    before = h.sim.host_syncs
    summary = h.run()
    assert summary["abandoned_rounds"] == 0
    per_round = 3 if engine == "eager" else 0
    per_chunk = 0 if engine == "eager" else 3
    assert h.sim.host_syncs - before == (
        rounds * per_round + (rounds // chunk) * per_chunk + 1)


ASYNC = {"name": "async", "buffer_size": 3, "max_concurrency": 4}


@pytest.mark.parametrize("policy,rounds,chunk", [
    ({"name": "sync"}, 6, 3),
    (ASYNC, 6, 3),
    ({"name": "sync"}, 7, 3),
], ids=["sync", "async", "partial-last-chunk"])
def test_scan_objectives_are_the_objective_at_each_broadcast_point(
        monkeypatch, policy, rounds, chunk):
    """The scan path's batched read reports, round by round, exactly what
    ``handle.objective`` reads at that round's broadcast point, and the
    summary's f_final is the last of them."""
    streams = []

    def recording_run_rounds(*args, **kw):
        res = run_rounds(*args, **kw)
        streams.append(res.w_tau)
        return res

    monkeypatch.setattr(sys.modules[RunHandle.__module__], "run_rounds",
                        recording_run_rounds)
    h = _spec("scan", rounds, chunk=chunk, policy=policy).build()
    fs = []
    summary = h.run(report=lambda met, f: fs.append(f))
    ws = np.concatenate(streams)
    assert len(streams) == -(-rounds // chunk) and ws.shape[0] == rounds
    want = [float(h.objective(jnp.asarray(w))) for w in ws]
    assert all(type(f) is float for f in fs)
    assert fs == want
    assert summary["f_final"] == want[-1] / h.spec.task.m


def test_a_second_handle_reuses_the_batched_objective():
    """A new RunHandle over the same task (as a timed window builds one)
    takes the compiled batched objective from the cache and compiles it
    for no new shape."""
    spec = _spec("scan", 4, chunk=2)
    h = spec.build()
    h.run()
    again = RunHandle(spec=spec, sim=h.sim, data=h.data)
    assert again._fobjs is h._fobjs
    compiled = h._fobjs._cache_size()
    again.run()
    assert h._fobjs._cache_size() == compiled


def test_host_syncs_count_the_lm_summary_read():
    """An LM pytree has no per-round objective on the scan path: the
    summary reads f once, after one candidate stream per chunk."""
    h = _lm("scan", 2, chunk=1).build()
    before = h.sim.host_syncs
    h.run()
    assert h.sim.host_syncs - before == 2 + 1
