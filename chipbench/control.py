#!/usr/bin/env python3
"""Readings that the limits of ``correct`` are set from, on the chip.

    python3 chipbench/control.py --workload paper-logreg.sync \
        --seeds 101 102 ... --control-seeds 101 102 103

For every seed of ``--seeds``: the program's first chunk through ``run()``
(as every benchmark run takes it), then the plain reference over the same
rounds, and the gaps between the two. For every control seed also the
gaps of the control (the reference in the next lower precision, put in
the program's place) and of the planted faults, each in the reference
put in the program's place: every client's loss over half of its rows,
and on a cell of more than one chip the exchange between chips left out
of the aggregate. A step that returns its state unchanged needs no run:
its ``change_gap`` is 1. The reference runs on the cell's chips. One
JSON line per reading, and last the peak device memory of each chip:
with no ``--seeds`` the reference's own. The whole set is written to
``chipbench/out/control/<workload>.json``.
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
    ap.add_argument("--seeds", type=int, nargs="*", default=[])
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
    cell, _, cfg, mix = bench_run.cell_files(manifest, args.workload)
    chips = cell["chips"]
    task = harness.config_module(cfg["_file"])
    mix_ref = harness.mix_reference(mix)
    chunk = cfg["spec"]["engine"]["chunk"]
    faults = [("control", {"lower": True}),
              ("half_batch", {"fault": "half_batch"})]
    if chips > 1:
        faults.append(("no_exchange", {"fault": "no_exchange"}))
    out = {"workload": args.workload, "program": {},
           **{kind: {} for kind, _ in faults}}

    def line(kind, seed, vals, s):
        out[kind][seed] = vals
        print(json.dumps({"kind": kind, "seed": seed, "s": s, **vals}),
              flush=True)

    for seed in dict.fromkeys(args.seeds + args.control_seeds):
        t = time.perf_counter()
        spec = harness.spec_dict(cfg, mix, seed, chunk)
        if seed in args.seeds:
            _, handle, prog = harness.first_chunk(cfg, mix, seed)
            del handle
            harness.drop_program_caches()
        ref = mix_ref.run_reference(task, cfg, spec, seed, chunk,
                                    chips=chips)
        if seed in args.seeds:
            line("program", seed, reference.compare(prog, ref),
                 time.perf_counter() - t)
        if seed in args.control_seeds:
            for kind, kw in faults:
                t = time.perf_counter()
                other = mix_ref.run_reference(task, cfg, spec, seed, chunk,
                                              chips=chips, **kw)
                line(kind, seed, reference.compare(other, ref),
                     time.perf_counter() - t)
                gc.collect()
    import jax
    out["peak_bytes"] = [(d.memory_stats() or {}).get("peak_bytes_in_use")
                         for d in jax.devices()[:chips]]
    print(json.dumps({"kind": "memory", "peak_bytes": out["peak_bytes"]}),
          flush=True)
    dest = BENCH_DIR / "out" / "control"
    dest.mkdir(parents=True, exist_ok=True)
    (dest / f"{args.workload}.json").write_text(json.dumps(out, indent=1))
    return 0


if __name__ == "__main__":
    sys.exit(main())
