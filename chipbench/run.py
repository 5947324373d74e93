#!/usr/bin/env python3
"""Run one benchmark cell on the accelerator this process finds.

    python3 chipbench/run.py --workload smollm-135m.sync --seed 7 \
        --seconds 10 --trace 0

Reads the cell from ``BENCHMARK.json`` at the checkout's root. With
``--trace 0`` the result line carries the cell's end-to-end metrics, with
``--trace 1`` its per-layer metrics read from a profiler trace of the
window. Every run checks the program against the plain reference and
prints each compared number beside its limit, as the last lines on
standard error and under ``checks`` at the end of the result line, which
is the last line on standard output.

Without an accelerator, with fewer chips than the cell asks for, or on a
device missing from ``peaks.json``, it exits 1 and prints no result.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import pathlib  # noqa: E402
import sys  # noqa: E402

BENCH_DIR = pathlib.Path(__file__).resolve().parent
ROOT = BENCH_DIR.parent
CACHE_DIR = BENCH_DIR / ".cache" / "jax"


def fail(msg: str) -> int:
    print(f"chipbench: {msg}", file=sys.stderr)
    return 1


def cell_files(manifest: dict, workload: str):
    """-> (cell, config entry, config dict, mix dict) by name. A cell whose
    ``chips`` are not the chips its configuration's client axis spans
    (``harness.mesh_chips``) is a ValueError."""
    from harness import load_json, mesh_chips
    cells = {w["name"]: w for w in manifest["workloads"]}
    if workload not in cells:
        raise KeyError(f"no workload {workload!r} in BENCHMARK.json")
    cell = cells[workload]
    entry = {c["name"]: c for c in manifest["configs"]}[cell["config"]]
    cfg = load_json(ROOT / entry["file"])
    cfg["_file"] = ROOT / entry["file"]
    if cell["chips"] != mesh_chips(cfg):
        raise ValueError(f"{workload} asks for {cell['chips']} chips; its "
                         f"configuration's engine.mesh spans "
                         f"{mesh_chips(cfg)}")
    mix_file = BENCH_DIR / "mixes" / f"{cell['traffic']}.json"
    mix = load_json(mix_file)
    mix["_file"] = mix_file
    return cell, entry, cfg, mix


def peak_row(peaks: dict, kind: str) -> dict:
    """The peaks of one device kind; a kind not in the table is an error."""
    if kind not in peaks or kind.startswith("_"):
        raise ValueError(f"no peaks for device kind {kind!r} in peaks.json")
    return peaks[kind]


def settle_cache(cache_dir: pathlib.Path, workload: str) -> None:
    """Leave the persistent compilation cache as each cell's first run in
    this checkout left it.

    The program compiles its chunk with the seed's data as constants, so
    every seed has programs of its own, and a run whose seed ran before in
    this checkout would find them cached and set up faster than one whose
    seed did not. A cell's first run that ends here writes the list of the
    cache's files; every later run deletes, as it ends, the files that no
    cell's list names. Every run then sets up from the same cache: the
    programs that do not depend on the seed are in it, those of its own
    seed are not (the first run's seed excepted).
    """
    if not cache_dir.is_dir():
        return
    names = sorted(p.name for p in cache_dir.iterdir())
    lists = cache_dir.parent / f"{cache_dir.name}.first-run"
    lists.mkdir(exist_ok=True)
    mine = lists / f"{workload}.json"
    if not mine.exists():
        mine.write_text(json.dumps(names))
        return
    keep = set()
    for f in lists.glob("*.json"):
        keep.update(json.loads(f.read_text()))
    for name in names:
        if name not in keep:
            (cache_dir / name).unlink(missing_ok=True)


def metrics_of(manifest: dict, workload: str, kind: str) -> list[dict]:
    """The cell's metrics of one kind (``end_to_end`` or ``per_layer``)."""
    return [m for m in manifest[kind]
            if workload in m.get("workloads", [workload])]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    if args.seed < 0:
        return fail(f"--seed must be >= 0; got {args.seed}")

    sys.path[:0] = [str(BENCH_DIR), str(ROOT / "src")]
    manifest_path = ROOT / "BENCHMARK.json"
    if not manifest_path.exists():
        return fail("no BENCHMARK.json at the checkout's root")
    import harness
    manifest = harness.load_json(manifest_path)
    try:
        cell, entry, cfg, mix = cell_files(manifest, args.workload)
    except (KeyError, FileNotFoundError, ValueError) as e:
        return fail(str(e))
    peaks = harness.load_json(BENCH_DIR / "peaks.json")

    # the benchmark's own cache, at a fixed path inside the checkout
    os.environ["JAX_COMPILATION_CACHE_DIR"] = str(CACHE_DIR)
    # the TPU runtime's logs stay inside the checkout, not in /tmp
    os.environ.setdefault("TPU_LOG_DIR", str(harness.TRACE_DIR.parent
                                             / "tpu_logs"))
    try:
        import jax
    except ImportError as e:
        return fail(f"cannot import jax: {e}")
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    devs = jax.devices()
    if devs[0].platform == "cpu":
        return fail("needs an accelerator; JAX found only the CPU")
    if len(devs) < cell["chips"]:
        return fail(f"{args.workload} needs {cell['chips']} chips; JAX "
                    f"found {len(devs)}")
    try:
        peak_row(peaks, devs[0].device_kind)
    except ValueError as e:
        return fail(str(e))
    try:
        import repro  # noqa: F401
    except ImportError as e:
        return fail(f"the program is not in this checkout: {e}")

    trace_dir = harness.TRACE_DIR / f"{args.workload}-{args.seed}"
    run = harness.run_window(cfg, mix, seed=args.seed, seconds=args.seconds,
                             trace=bool(args.trace), t_start=T_START,
                             trace_dir=trace_dir)
    if run["compiles_in_window"]:
        print(f"chipbench: {run['compiles_in_window']} program(s) compiled "
              "inside the window", file=sys.stderr)
    correct, checks = harness.check(cfg, mix, args.seed, run["prog"],
                                    cell["chips"])
    settle_cache(CACHE_DIR, args.workload)
    finite = run["f_final"] == run["f_final"] and abs(run["f_final"]) < 1e38
    failed = 0 if finite else run["rounds"]
    kind = "per_layer" if args.trace else "end_to_end"
    metrics = harness.read_metrics(harness.metric_context(cfg, run, peaks),
                                   metrics_of(manifest, args.workload, kind))
    device = {**run["device"], "memory_peak_bytes": run["memory_peak_bytes"]}
    result = {"correct": bool(correct and finite),
              "attempted": run["rounds"], "failed": failed,
              "metrics": metrics, "device": device}
    if args.trace:
        tr = run["trace"] or {}
        device.update(busy_s=tr.get("busy_s", 0.0),
                      window_s=tr.get("window_s", run["window_s"]))
        if tr:
            result["breakdown"] = {"device_ops": tr["device_ops"],
                                   "idle_gaps": tr["idle_gaps"]}
    result["checks"] = checks
    for name, c in checks.items():
        print(f"check {name} {c['value']!r} limit {c['limit']!r}",
              file=sys.stderr)
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
