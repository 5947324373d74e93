"""One run of one benchmark cell: set-up, the measured window, the check.

The cell's configuration (``configs/<name>.json`` with its plain reference
``configs/<name>.py``), its traffic mix (``mixes/<name>.json`` with the
plain reference of its policy ``mixes/<name>.py``) and each metric's reader
(``metrics/<name>.py``, end-to-end and per-layer alike) are found by the
names ``BENCHMARK.json`` gives, so a new cell, mix or metric is new files
only.

The path under test is ``ExperimentSpec(...).build().run()`` on the scan
engine. Set-up builds ONE handle from the seed and drives it through:

1. a first ``run()`` of one chunk of rounds (this compiles, and its
   results are what the check compares with the reference);
2. at most ``CALIBRATE_MAX_RUNS`` further ``run()`` calls of one chunk,
   timed, to size the window (few, since a configuration's horizon
   ``max_rounds`` counts them too).

The window is ONE more ``run()`` on the same simulator, with a round budget
that is a whole multiple of the chunk, so every program it launches was
compiled in set-up. It ends with the state blocked until ready. Only then
is device memory read, the program's state dropped, and the reference run.
"""
from __future__ import annotations

import gc
import importlib.util
import json
import math
import pathlib
import time

import numpy as np

BENCH_DIR = pathlib.Path(__file__).resolve().parent
ROOT = BENCH_DIR.parent
TRACE_DIR = BENCH_DIR / "out" / "traces"
# a traced run traces a window of this share of --seconds (at least one
# chunk): the profiler's buffers drop device events past about 6M, which a
# whole 10 s window of the LM cell exceeds
TRACE_SHARE = 0.2
CALIBRATE_S = 1.0
CALIBRATE_MAX_RUNS = 4
COMPILE_EVENTS = ("/jax/core/compile/backend_compile_duration",
                  "/jax/compilation_cache/cache_hits")


def load_module(path: pathlib.Path, name: str):
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def load_json(path: pathlib.Path) -> dict:
    with open(path) as f:
        return json.load(f)


def config_module(config_file: pathlib.Path):
    """The plain reference beside a configuration's JSON file."""
    return load_module(config_file.with_suffix(".py"),
                       f"chipbench_config_{config_file.stem}")


def mix_reference(mix: dict):
    """The plain reference of a traffic mix's policy, beside its JSON."""
    return load_module(mix["_file"].with_suffix(".py"),
                       f"chipbench_mix_{mix['_file'].stem}")


def metric_reader(name: str):
    return load_module(BENCH_DIR / "metrics" / f"{name}.py",
                       f"chipbench_metric_{name}").read


# what the program keeps per seed across handles (task data, compiled
# chunks with their constants): (module, attribute) of each dict
PROGRAM_CACHES = (("repro.spec.build", "_TASK_CACHE"),
                  ("repro.spec.build", "_OBJ_CACHE"),
                  ("repro.sim.server", "_JIT_CACHE"),
                  ("repro.sim.engine", "_CHUNK_FN_CACHE"),
                  ("repro.sim.engine", "_CAND_STREAM_CACHE"))


def drop_program_caches() -> None:
    """Free what the program keeps per seed, so that many seeds fit in one
    process and each builds its programs anew."""
    import importlib

    import jax
    for mod, name in PROGRAM_CACHES:
        getattr(importlib.import_module(mod), name).clear()
    jax.clear_caches()
    gc.collect()


def mesh_chips(cfg: dict) -> int:
    """The chips a configuration's client axis spans: its ``engine.mesh``,
    or 1 where it sets none. A cell's ``chips`` has to be this."""
    return int(cfg["spec"].get("engine", {}).get("mesh") or 1)


def spec_dict(cfg: dict, mix: dict, seed: int, rounds: int) -> dict:
    """The ExperimentSpec of one cell: the configuration's sections
    (task, algorithm, engine chunk) and the mix's (fleet, policy, ...)."""
    sections = {**cfg["spec"], **mix["spec"]}
    engine = dict(sections.pop("engine", {}))
    engine.update(name="scan", rounds=rounds)
    return {"name": f"chipbench/{cfg['name']}/{mix['name']}", "seed": seed,
            **sections, "engine": engine}


class CompileCounter:
    """Counts programs compiled or fetched from the persistent cache."""

    def __init__(self):
        from jax import monitoring
        self.n = 0
        monitoring.register_event_duration_secs_listener(self._duration)
        monitoring.register_event_listener(self._event)

    def _duration(self, name, secs, **kw):
        if name == COMPILE_EVENTS[0]:
            self.n += 1

    def _event(self, name, **kw):
        if name == COMPILE_EVENTS[1]:
            self.n += 1


def say(**kw) -> None:
    print(json.dumps(kw), flush=True)


def _block(tree):
    import jax
    jax.block_until_ready(tree)


def capture(handle, summary: dict, fs: list, chunk: int, task) -> dict:
    """The program's readings after its first chunk, as the check wants
    them (host numbers only)."""
    from reference import leaf_change_norms
    sim = handle.sim
    m = handle.spec.task.m
    f = fs if task.OBJECTIVE_EVERY_ROUND else [summary["f_final"] * m]
    rounds = [{"n_contacted": mm.n_contacted, "n_aggregated": mm.n_aggregated,
               "t_round": mm.t_round, "bytes_down": mm.bytes_down,
               "bytes_up": mm.bytes_up, "abandoned": mm.abandoned}
              for mm in sim.metrics[-chunk:]]
    return {"f": [float(x) for x in f], "rounds": rounds, "t": sim.t,
            "bytes_up": sim.ledger.total_up,
            "bytes_down": sim.ledger.total_down,
            "grad_l1": np.asarray(sim.last_round_metrics.grad_l1,
                                  np.float64),
            "change": leaf_change_norms(sim.state.w_tau, handle.data.params0)}


def first_chunk(cfg: dict, mix: dict, seed: int):
    """Build the cell's handle from the seed and run its first chunk:
    -> (spec, handle, the program's readings for the check)."""
    from repro.spec import ExperimentSpec

    task = config_module(cfg["_file"])
    chunk = cfg["spec"]["engine"]["chunk"]
    spec = ExperimentSpec.from_dict(spec_dict(cfg, mix, seed, chunk))
    t = time.perf_counter()
    handle = spec.build()
    say(phase="build", s=time.perf_counter() - t)
    fs: list = []
    t = time.perf_counter()
    first = handle.run(report=lambda met, f: fs.append(f))
    _block(handle.sim.state)
    say(phase="first_chunk", s=time.perf_counter() - t, rounds=chunk)
    return spec, handle, capture(handle, first, fs, chunk, task)


def run_window(cfg: dict, mix: dict, *, seed: int, seconds: float,
               trace: bool, t_start: float, trace_dir=None) -> dict:
    """Set-up, then the window. -> everything the result line needs, and
    the program's first-chunk readings for the check."""
    import jax

    from repro.spec.build import RunHandle

    counter = CompileCounter()
    chunk = cfg["spec"]["engine"]["chunk"]
    spec, handle, prog = first_chunk(cfg, mix, seed)

    def handle_for(h, rounds):
        return RunHandle(spec=spec.replace(**{"engine.rounds": rounds}),
                         sim=h.sim, data=h.data)

    # time chunks until CALIBRATE_S have been timed or CALIBRATE_MAX_RUNS
    # run, leaving out any run that compiled (the second run() of a handle
    # compiles one program again), and size the window from their median
    times: list[float] = []
    for _ in range(CALIBRATE_MAX_RUNS):
        n0 = counter.n
        t = time.perf_counter()
        handle_for(handle, chunk).run()
        _block(handle.sim.state)
        if counter.n == n0:
            times.append(time.perf_counter() - t)
        if sum(times) >= CALIBRATE_S:
            break
    t_chunk = float(np.median(times)) if times else time.perf_counter() - t
    span = seconds * (TRACE_SHARE if trace else 1.0)
    n_chunks = max(1, round(span / t_chunk))
    if "max_rounds" in cfg:
        # the configuration's horizon: past it the program's own numbers
        # stop being finite (see the configuration file)
        n_chunks = max(1, min(n_chunks, (cfg["max_rounds"]
                                         - handle.sim.round_idx) // chunk))
    rounds = n_chunks * chunk
    say(phase="calibrate", s_per_chunk=t_chunk, chunk=chunk,
        rounds_per_s=chunk / t_chunk, window_rounds=rounds)

    window = handle_for(handle, rounds)
    n_compiled = counter.n
    # set-up's garbage (traced programs) is collected now, not in the window
    gc.collect()
    gc.freeze()
    tr = None
    if trace:
        import shutil
        shutil.rmtree(trace_dir, ignore_errors=True)
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        opts.host_tracer_level = 2
        jax.profiler.start_trace(str(trace_dir), profiler_options=opts)
    setup_s = time.perf_counter() - t_start
    t0 = time.perf_counter()
    with jax.profiler.TraceAnnotation("chipbench.window"):
        summary = window.run()
        _block(handle.sim.state)
    window_s = time.perf_counter() - t0
    if trace:
        jax.profiler.stop_trace()
    compiles = counter.n - n_compiled
    say(phase="window", s=window_s, rounds=summary["rounds"],
        compiles_in_window=compiles, f_final=summary["f_final"],
        sim_time_s=summary["sim_time_s"], bytes_total=summary["bytes_total"],
        round_idx=handle.sim.round_idx)
    dev = jax.devices()[0]
    peak = max((d.memory_stats() or {}).get("peak_bytes_in_use", 0)
               for d in jax.devices())
    out = {"setup_s": setup_s, "window_s": window_s,
           "rounds": summary["rounds"], "f_final": summary["f_final"],
           "summary": summary,
           "compiles_in_window": compiles, "memory_peak_bytes": peak,
           "prog": prog,
           "device": {"platform": dev.platform, "kind": dev.device_kind,
                      "count": len(jax.devices())}}
    del window, handle
    gc.collect()
    if trace:
        import tracing
        t = time.perf_counter()
        path = tracing.find_xplane(trace_dir)
        tr = tracing.read_xplane(path) if path else None
        say(phase="trace", s=time.perf_counter() - t,
            bytes=path.stat().st_size if path else 0)
        if path:
            path.unlink()
    out["trace"] = tr
    return out


def check(cfg: dict, mix: dict, seed: int, prog: dict,
          chips: int = 1) -> tuple[bool, dict]:
    """Run the mix's reference over the first chunk on the cell's
    ``chips``; -> (correct, checks)."""
    import reference
    task = config_module(cfg["_file"])
    chunk = cfg["spec"]["engine"]["chunk"]
    spec = spec_dict(cfg, mix, seed, chunk)
    t = time.perf_counter()
    ref = mix_reference(mix).run_reference(task, cfg, spec, seed, chunk,
                                           chips=chips)
    vals = reference.compare(prog, ref)
    say(phase="check", s=time.perf_counter() - t)
    limits = cfg["limits"]
    checks = {k: {"value": v, "limit": limits[k]} for k, v in vals.items()}
    ok = all(math.isfinite(c["value"]) and c["value"] <= c["limit"]
             for c in checks.values())
    return ok, checks


def metric_context(cfg: dict, run: dict, peaks: dict) -> dict:
    """What a metric's reader reads: the window's host clock and counts,
    the program's summary of it, the device's memory counter, the reduced
    trace (traced runs), the configuration's FLOPs and tokens per round,
    and the device's peaks."""
    task = config_module(cfg["_file"])
    return {"trace": run.get("trace"), "rounds": run["rounds"],
            "window_s": run["window_s"], "setup_s": run["setup_s"],
            "summary": run["summary"],
            "memory_peak_bytes": run["memory_peak_bytes"],
            "flops_per_round": task.flops_per_round(cfg),
            "tokens_per_round": task.tokens_per_round(cfg),
            "peak": peaks[run["device"]["kind"]],  # run.peak_row checked it
            "chips": run["device"]["count"]}


def read_metrics(ctx: dict, metrics: list[dict]) -> dict:
    """Each metric from its own reader; a reader that finds nothing to
    read returns None and the metric is left out."""
    out = {}
    for met in metrics:
        v = metric_reader(met["name"])(ctx)
        if v is not None:
            out[met["name"]] = {"value": v, "unit": met["unit"]}
    return out
