#!/usr/bin/env python3
"""Run FedEPM's main path once on a TPU and check what comes out.

    python chip_smoke.py               # one chip: every phase below
    python chip_smoke.py --four-chips  # four chips: the engine.mesh=4 path

Everything runs in this one process, which is the only one to touch JAX;
no child process is started. Without a TPU it exits 1 before running any
phase: it never falls back to the CPU, to interpret mode or to a ``ref``
implementation. Any failed check exits nonzero, and only a run in which
every phase passed prints the last line
``{"ok": true, "device": {"platform": "tpu", "kind": ..., "count": N}}``.

One-chip phases; all but the first run through
``ExperimentSpec(...).build().run()``:

kernels       the six Pallas kernels against their jnp references at the
              paper's shapes (m=50 clients, n=14)
paper-sync    the paper's logreg task at its width (configs/paper_logreg:
              d=45222, n=14, m=50, rho=0.5, k0=8; 20 rounds), sync policy,
              scan engine, ENS + prox Pallas kernels
fig7-async    examples/specs/fig7_async.toml at d=45222: async
              record/replay, scan engine, Pallas upload codec
fig9-privacy  examples/specs/fig9_privacy.toml at d=45222: scan engine,
              the fused clip + noise + quantize kernel
lm            examples/specs/lm_federated.toml at smollm-135m's published
              widths (30 layers, d_model 576, vocab 49152), m=4 clients,
              2 sequences of 256 tokens each, 3 scan rounds

The three logreg phases are compared with the same spec on the eager
engine with the ``ref`` implementations: ``f_final`` to ``REL_TOL`` and
every other summary field (byte ledger, simulated clock, staleness,
privacy accounting) equal. Every Pallas kernel a phase called is lowered
again at the shapes it was called with, and its compiled text must hold a
``tpu_custom_call``. The LM phase checks its compiled program's memory
against the device before it runs; its loss must be finite and must not
rise. The LM spec runs with ``eps_dp = 0``: the file's per-coordinate
Laplace noise (scale clip / (eps * mu) = 0.1) outweighs three rounds of
training, and the privacy phase covers the noisy upload path.

``--four-chips`` runs only the mesh phase: the LM spec and the fig7 spec
with ``engine.mesh = 4`` against the same spec unsharded; the per-round
LM loss must agree to ``LM_MESH_REL_TOL``, the fig7 ``f_final`` to
``REL_TOL`` and its byte ledger exactly. The fig7 spec keeps the ``ref``
codec there, because a Pallas (Mosaic) kernel cannot be partitioned
automatically over a mesh.
"""
from __future__ import annotations

import argparse
import contextlib
import importlib
import json
import math
import pathlib
import sys
import time

ROOT = pathlib.Path(__file__).resolve().parent
SPECS = ROOT / "examples" / "specs"

REL_TOL = 1e-5          # f_final, float32 logreg: scan+Pallas vs eager+ref
LM_MESH_REL_TOL = 1e-3  # per-round LM loss (bf16 activations): mesh vs none
PAPER_D = 45222

# name -> (module, attribute) of each kernel's jitted pallas_call wrapper
KERNELS = {
    "ens": ("repro.kernels.ens.ens", "_ens_call"),
    "prox": ("repro.kernels.prox.prox", "_prox_call"),
    "quantize": ("repro.kernels.quant.quant", "_quant_call"),
    "ef_accumulate": ("repro.kernels.quant.ef", "_ef_call"),
    "quantize_cols": ("repro.kernels.quant.batch", "_quant_cols_call"),
    "private_quantize_cols": ("repro.kernels.quant.privacy",
                              "_private_cols_call"),
}


class SmokeFailure(RuntimeError):
    pass


def check(cond: bool, msg: str) -> None:
    if not cond:
        raise SmokeFailure(msg)


def rel_diff(a: float, b: float) -> float:
    return abs(a - b) / max(abs(b), 1e-30)


def say(phase: str, **fields) -> None:
    print(json.dumps({"phase": phase, **fields}), flush=True)


# ---------------------------------------------------------------------------
# kernel bookkeeping
# ---------------------------------------------------------------------------

@contextlib.contextmanager
def recorded_kernel_calls():
    """Record every kernel call (shapes, static args) made inside."""
    import jax

    calls: dict = {}
    saved = []
    for name, (mod_name, attr) in KERNELS.items():
        mod = importlib.import_module(mod_name)
        fn = getattr(mod, attr)
        saved.append((mod, attr, fn))

        def record(*args, _name=name, _fn=fn, **kw):
            shapes = tuple(jax.ShapeDtypeStruct(a.shape, a.dtype)
                           for a in args)
            key = (_name, tuple((s.shape, str(s.dtype)) for s in shapes),
                   tuple(sorted(kw.items())))
            calls.setdefault(key, (_fn, shapes, kw))
            return _fn(*args, **kw)

        setattr(mod, attr, record)
    try:
        yield calls
    finally:
        for mod, attr, fn in saved:
            setattr(mod, attr, fn)


def check_compiled(calls: dict, expect: set) -> list:
    """Each recorded call ran compiled and lowers to a tpu_custom_call."""
    names = {key[0] for key in calls}
    check(expect <= names, f"kernels never called: {sorted(expect - names)}")
    seen = []
    for (name, shapes, _), (fn, abstract, kw) in calls.items():
        check(kw.get("interpret") is False,
              f"{name} ran with interpret={kw.get('interpret')}")
        text = fn.lower(*abstract, **kw).compile().as_text()
        check("tpu_custom_call" in text,
              f"{name} at {shapes}: no tpu_custom_call in compiled text")
        seen.append(f"{name}{[list(s) for s, _ in shapes]}")
    return sorted(seen)


# ---------------------------------------------------------------------------
# phases
# ---------------------------------------------------------------------------

def kernels_vs_ref(m: int = 50, n: int = 14, bits: int = 8) -> None:
    """Each kernel, compiled, against its jnp reference on random data:
    ENS and prox to ``REL_TOL``, the four quantizers bit for bit (the
    contract of docs/kernels.md; a one-step slack would hide a quantizer
    that rounds the wrong way).
    """
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.kernels.ens import ops as ens_ops
    from repro.kernels.prox import ops as prox_ops
    from repro.kernels.quant import ops as quant_ops

    ks = jax.random.split(jax.random.PRNGKey(0), 6)
    X = jax.random.normal(ks[0], (m, n))
    H = 0.5 * jax.random.normal(ks[1], (m, n))
    u32 = jax.random.bits(ks[2], (m, n), jnp.uint32)
    lap = jax.random.laplace(ks[3], (m, n))
    kcols = jax.random.randint(ks[4], (m,), 1, n + 1)
    w = jax.random.normal(ks[5], (n,))
    cf, b = jnp.full((m,), 0.5), jnp.full((m,), 0.1)
    scale = jnp.max(jnp.abs(X), axis=1)
    r_scale = jnp.max(jnp.abs(X - H), axis=1)

    # name -> (call(impl), exact)
    cases = {
        "ens": (lambda impl: ens_ops.ens(X, 1e-5, 2e-5, impl=impl), False),
        "prox": (lambda impl: prox_ops.prox_update(
            X[0], w, H[0], 0.05, 1e-5, 2e-5, impl=impl), False),
        "quantize": (lambda impl: quant_ops.quantize(
            X, scale, bits, u32, impl=impl), True),
        "ef_accumulate": (lambda impl: quant_ops.ef_accumulate(
            X, H, r_scale, bits, u32, impl=impl), True),
        "quantize_cols": (lambda impl: quant_ops.quantize_cols(
            X, H, scale, kcols, bits, u32, impl=impl), True),
        "private_quantize_cols": (lambda impl:
            quant_ops.private_quantize_cols(
                X, H, cf, b, scale * cf, kcols, bits, u32, lap, impl=impl),
            True),
    }
    out = {}
    for name, (run, exact) in cases.items():
        with recorded_kernel_calls() as calls:
            got = np.asarray(run("pallas"))
        check_compiled(calls, {name})
        want = np.asarray(run("ref"))
        check(got.shape == want.shape and np.isfinite(got).all(),
              f"{name}: shape {got.shape} vs {want.shape}, or not finite")
        diff = np.abs(got - want)
        ok = (np.array_equal(got, want) if exact
              else np.allclose(got, want, rtol=REL_TOL, atol=1e-6))
        check(ok, f"{name}: kernel vs ref max |diff| {diff.max()}")
        out[name] = {"max_abs_diff": float(diff.max()),
                     "n_differ": int((got != want).sum()),
                     "n": int(got.size)}
    say("kernels", m=m, n=n, results=out)


def paper_spec():
    from repro.spec import (AlgorithmSpec, EngineSpec, ExperimentSpec,
                            PolicySpec, TaskSpec)
    return ExperimentSpec(
        name="paper/fedepm/sync",
        task=TaskSpec(kind="logreg", d=PAPER_D, n=14, m=50),
        algorithm=AlgorithmSpec(name="fedepm", rho=0.5, k0=8),
        policy=PolicySpec(name="sync"),
        engine=EngineSpec(name="eager", rounds=20))


def example_spec(file: str, **overrides):
    from repro.spec import ExperimentSpec
    return ExperimentSpec.load(SPECS / file).replace(**overrides)


def engines_agree(phase: str, spec, fast: dict, expect: set) -> None:
    """``spec`` on the eager engine with ref impls vs ``fast`` overrides
    (scan engine + Pallas impls)."""
    ref_spec = spec.replace(**{"engine.name": "eager"})
    check(ref_spec.codec.impl == "ref"
          and ref_spec.algorithm.ens_impl in (None, "ref")
          and ref_spec.algorithm.prox_impl in (None, "ref"),
          f"{phase}: reference spec must use the ref impls")
    t0 = time.perf_counter()
    want = ref_spec.build().run()
    t_ref = time.perf_counter() - t0
    with recorded_kernel_calls() as calls:
        t0 = time.perf_counter()
        got = spec.replace(**fast).build().run()
        t_fast = time.perf_counter() - t0
    check(math.isfinite(got["f_final"]), f"{phase}: f_final not finite")
    rel = rel_diff(got["f_final"], want["f_final"])
    check(rel <= REL_TOL, f"{phase}: f_final {got['f_final']!r} vs eager "
          f"{want['f_final']!r} (rel {rel:.3g} > {REL_TOL})")
    for key in sorted(set(got) | set(want)):
        if key in ("engine", "f_final", "accuracy"):
            continue
        check(got.get(key) == want.get(key),
              f"{phase}: summary[{key!r}] {got.get(key)!r} vs eager "
              f"{want.get(key)!r}")
    kernels = check_compiled(calls, expect)
    say(phase, f_final=got["f_final"], f_final_eager=want["f_final"],
        rel_diff=rel, accuracy=got["accuracy"], rounds=got["rounds"],
        bytes_total=got["bytes_total"], wall_s_scan=t_fast,
        wall_s_eager=t_ref, kernels=kernels)


def lm_spec():
    return example_spec("lm_federated.toml", **{
        "name": "lm/smollm-135m/sync", "task.reduced": False,
        "task.m": 4, "task.batch_per_client": 2, "task.seq_len": 256,
        "algorithm.eps_dp": 0.0,
        "engine.name": "scan", "engine.rounds": 3, "engine.chunk": 1})


def lm_losses(spec, dev=None) -> tuple[list, dict]:
    """Initial + per-round loss/m of the LM spec; with ``dev``, check the
    compiled round's memory against it first."""
    import jax

    from repro.sim import lower_rounds

    handle = spec.build()
    m = spec.task.m
    info = {"params": sum(x.size for x in
                          jax.tree_util.tree_leaves(handle.data.params0))}
    if dev is not None:
        t0 = time.perf_counter()
        compiled = lower_rounds(handle.sim, 1).compile()
        info["compile_s"] = time.perf_counter() - t0
        ma = compiled.memory_analysis()
        need = (ma.argument_size_in_bytes + ma.output_size_in_bytes
                - ma.alias_size_in_bytes + ma.temp_size_in_bytes)
        limit = dev.memory_stats()["bytes_limit"]
        info.update(program_bytes=need, bytes_limit=limit)
        check(need < limit, f"LM round needs {need} bytes; the device "
              f"has {limit}")
        del compiled
    losses = [float(handle.objective(handle.sim.state.w_tau)) / m]

    def report(met, f):
        losses.append(float(handle.objective(handle.sim.state.w_tau)) / m)

    t0 = time.perf_counter()
    handle.run(report=report)
    info["run_s"] = time.perf_counter() - t0
    check(all(math.isfinite(x) for x in losses),
          f"LM loss not finite: {losses}")
    return losses, info


def lm_phase(dev) -> None:
    losses, info = lm_losses(lm_spec(), dev)
    check(all(b <= a for a, b in zip(losses, losses[1:]))
          and losses[-1] < losses[0], f"LM loss rose: {losses}")
    say("lm", arch="smollm-135m", loss_initial=losses[0],
        loss_per_round=losses[1:],
        peak_bytes_in_use=dev.memory_stats()["peak_bytes_in_use"], **info)


def four_chip_phase() -> None:
    losses, _ = lm_losses(lm_spec())
    mesh_losses, _ = lm_losses(lm_spec().replace(**{"engine.mesh": 4}))
    rel = max(rel_diff(a, b) for a, b in zip(mesh_losses, losses))
    check(len(mesh_losses) == len(losses) and rel <= LM_MESH_REL_TOL,
          f"LM mesh=4 losses {mesh_losses} vs unsharded {losses}")
    say("mesh-lm", loss_per_round=losses[1:],
        loss_per_round_mesh4=mesh_losses[1:], max_rel_diff=rel,
        tol=LM_MESH_REL_TOL)

    spec = example_spec("fig7_async.toml", **{"task.d": PAPER_D,
                                              "engine.name": "scan"})
    want = spec.build().run()
    got = spec.replace(**{"engine.mesh": 4}).build().run()
    rel = rel_diff(got["f_final"], want["f_final"])
    check(rel <= REL_TOL, f"fig7 mesh=4 f_final {got['f_final']!r} vs "
          f"{want['f_final']!r}")
    check(got["bytes_total"] == want["bytes_total"],
          f"fig7 mesh=4 bytes {got['bytes_total']} vs {want['bytes_total']}")
    say("mesh-fig7-async", f_final=got["f_final"],
        f_final_unsharded=want["f_final"], rel_diff=rel, tol=REL_TOL,
        bytes_total=got["bytes_total"])


def one_chip_phases(dev) -> None:
    kernels_vs_ref()
    engines_agree("paper-sync", paper_spec(),
                  {"engine.name": "scan", "algorithm.ens_impl": "pallas",
                   "algorithm.prox_impl": "pallas"}, {"ens", "prox"})
    engines_agree("fig7-async",
                  example_spec("fig7_async.toml", **{"task.d": PAPER_D}),
                  {"engine.name": "scan", "codec.impl": "pallas"},
                  {"quantize_cols"})
    engines_agree("fig9-privacy",
                  example_spec("fig9_privacy.toml", **{"task.d": PAPER_D}),
                  {"engine.name": "scan", "codec.impl": "pallas"},
                  {"private_quantize_cols"})
    lm_phase(dev)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the engine.mesh=4 phase (4 chips)")
    args = ap.parse_args(argv)

    sys.path.insert(0, str(ROOT / "src"))
    from repro.launch.compile_cache import enable_compile_cache
    cache_dir = enable_compile_cache()
    import jax

    devs = jax.devices()
    dev = devs[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: needs a TPU; JAX found {dev.platform}",
              file=sys.stderr)
        return 1
    want = 4 if args.four_chips else 1
    if len(devs) < want:
        print(f"chip_smoke: needs {want} chips; found {len(devs)}",
              file=sys.stderr)
        return 1
    say("device", platform=dev.platform, kind=dev.device_kind,
        count=len(devs), compile_cache=cache_dir)

    try:
        if args.four_chips:
            four_chip_phase()
        else:
            one_chip_phases(dev)
    except SmokeFailure as e:
        print(f"chip_smoke: FAILED: {e}", file=sys.stderr)
        return 1
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devs)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
