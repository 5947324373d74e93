#!/usr/bin/env python3
"""Readings that the limits of ``correct`` are set from, on the chip.

    python3 chipbench/control.py --workload paper-logreg.sync \
        --seeds 101 102 ... --control-seeds 101 102 103

For every seed: the program's first chunk through ``run()`` (as every
benchmark run takes it), then the plain reference over the same rounds,
and the gaps between the two. For every control seed also the gaps of
the control (the reference in the next lower precision, put in the
program's place) and of a planted fault (every client's loss over half
of its rows). A step that returns its state unchanged needs no run: its
``change_gap`` is 1. One JSON line per reading; the whole set is written
to ``chipbench/out/control/<workload>.json``.
"""
from __future__ import annotations

import argparse
import gc
import json
import os
import pathlib
import sys
import time

BENCH_DIR = pathlib.Path(__file__).resolve().parent
ROOT = BENCH_DIR.parent


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--control-seeds", type=int, nargs="*", default=[])
    args = ap.parse_args(argv)
    sys.path[:0] = [str(BENCH_DIR), str(ROOT / "src")]
    # a cache of its own: the benchmark's keeps only what its first run
    # wrote (run.settle_cache)
    os.environ.setdefault("JAX_COMPILATION_CACHE_DIR",
                          str(BENCH_DIR / ".cache" / "jax-control"))
    import harness
    import reference
    import run as bench_run

    manifest = harness.load_json(ROOT / "BENCHMARK.json")
    _, _, cfg, mix = bench_run.cell_files(manifest, args.workload)
    task = harness.config_module(cfg["_file"])
    mix_ref = harness.mix_reference(mix)
    chunk = cfg["spec"]["engine"]["chunk"]
    out = {"workload": args.workload, "program": {}, "control": {},
           "half_batch": {}}

    def line(kind, seed, vals, s):
        out[kind][seed] = vals
        print(json.dumps({"kind": kind, "seed": seed, "s": s, **vals}),
              flush=True)

    for seed in args.seeds:
        t = time.perf_counter()
        _, handle, prog = harness.first_chunk(cfg, mix, seed)
        del handle
        harness.drop_program_caches()
        spec = harness.spec_dict(cfg, mix, seed, chunk)
        ref = mix_ref.run_reference(task, cfg, spec, seed, chunk)
        line("program", seed, reference.compare(prog, ref),
             time.perf_counter() - t)
        if seed in args.control_seeds:
            for kind, kw in (("control", {"lower": True}),
                             ("half_batch", {"fault": "half_batch"})):
                t = time.perf_counter()
                other = mix_ref.run_reference(task, cfg, spec, seed, chunk,
                                              **kw)
                line(kind, seed, reference.compare(other, ref),
                     time.perf_counter() - t)
                gc.collect()
    dest = BENCH_DIR / "out" / "control"
    dest.mkdir(parents=True, exist_ok=True)
    (dest / f"{args.workload}.json").write_text(json.dumps(out, indent=1))
    return 0


if __name__ == "__main__":
    sys.exit(main())
