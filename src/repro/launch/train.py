"""Production training launcher: FedEPM as the distributed optimizer.

On a real TPU slice this runs under jax.distributed with the production
mesh; on this CPU host, pass --devices N to simulate N devices and a
proportionally reduced mesh (the same code path: pjit + shardings from
launch/steps.py).

    PYTHONPATH=src python -m repro.launch.train --arch smollm-135m \
        --devices 8 --mesh-shape 4,2 --rounds 3 --reduced

Federated mode (``--spec``): an lm-kind ExperimentSpec (docs/spec.md)
runs the arch through the SAME FedSim round loop as the logreg sim --
aggregation policies, device fleets, upload codecs, and the fused scan
engine all apply to the LM task, closing the "wire the sim into the
LM-scale launch path" roadmap item:

    PYTHONPATH=src python -m repro.launch.train \
        --spec examples/specs/lm_federated.toml
"""
import argparse
import os
import sys


def run_spec(args) -> int:
    """Federated-simulation mode: drive the spec's LM arch through
    FedSim/the scan engine (repro.spec.build.RunHandle)."""
    import time

    from repro.spec import ExperimentSpec, SpecError

    try:
        exp = ExperimentSpec.load(args.spec)
        if args.rounds_flag is not None:
            exp = exp.replace(**{"engine.rounds": args.rounds_flag})
        if args.engine_flag is not None:
            exp = exp.replace(**{"engine.name": args.engine_flag})
        exp.validate()
        if exp.task.kind != "lm":
            raise SpecError(
                f"train --spec expects an lm-kind task (this is the "
                f"LM-scale launcher); got kind={exp.task.kind!r} -- run "
                f"logreg specs via python -m repro.launch.simulate --spec")
        handle = exp.build()
    except SpecError as e:
        print(f"SPEC ERROR: {e}", file=sys.stderr)
        return 2
    import jax

    cfg = handle.data.aux["arch_cfg"]
    n_params = sum(x.size for x in jax.tree_util.tree_leaves(
        handle.data.params0))
    print(f"spec={exp.name} arch={cfg.name} params={n_params/1e6:.2f}M "
          f"m={exp.task.m} alg={exp.algorithm.name} "
          f"policy={exp.policy.name} engine={exp.engine.name} "
          f"rounds={exp.engine.rounds}")

    t0 = time.time()

    def report(met, f):
        loss_str = f"loss={f / exp.task.m:.4f}  " if f is not None else ""
        print(f"round {met.round_idx:3d}  {loss_str}"
              f"t_sim={met.t_total:.3f}s  "
              f"agg={met.n_aggregated}/{met.n_contacted}  "
              f"up={met.bytes_up/1e6:.2f}MB  ({time.time()-t0:.1f}s)",
              flush=True)

    summary = handle.run(report=report)
    print(f"\nfinal loss/m={summary['f_final']:.4f}  "
          f"sim_time={summary['sim_time_s']:.3f}s  "
          f"bytes_total={summary['bytes_total']:.0f}  "
          f"({time.time()-t0:.1f}s wall)")
    if args.json:
        import json
        with open(args.json, "w") as f:
            json.dump(summary, f, indent=1)
    if args.checkpoint:
        from repro.checkpoint import save
        save(args.checkpoint, jax.device_get(handle.sim.state.w_tau),
             {"arch": cfg.name, "spec": exp.name})
        print("saved", args.checkpoint)
    return 0


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--spec", default=None,
                    help="lm-kind ExperimentSpec file: run the arch "
                         "FEDERATED through the systems sim (FedSim + "
                         "eager/scan engine) instead of the pjit mesh "
                         "path; --rounds/--engine override the file")
    ap.add_argument("--engine", dest="engine_flag", default=None,
                    choices=["eager", "scan"],
                    help="(--spec only) round engine override")
    ap.add_argument("--json", default=None,
                    help="(--spec only) write the run summary dict here")
    ap.add_argument("--arch", default="smollm-135m")
    ap.add_argument("--rounds", dest="rounds_flag", type=int, default=None,
                    help="round budget (default: 3, or the --spec file's)")
    ap.add_argument("--reduced", action="store_true",
                    help="use the reduced config (CPU-sized)")
    ap.add_argument("--devices", type=int, default=0,
                    help="force host device count (0 = real devices)")
    ap.add_argument("--mesh-shape", default="",
                    help="data,model (default: production 16,16)")
    ap.add_argument("--ens", default="gather", choices=["gather", "a2a"])
    ap.add_argument("--k0", type=int, default=4)
    ap.add_argument("--seq", type=int, default=0,
                    help="override seq_len (CPU demos; 0 = production 4096)")
    ap.add_argument("--global-batch", type=int, default=0,
                    help="override global batch (0 = production 256)")
    ap.add_argument("--checkpoint", default=None)
    args = ap.parse_args(argv)
    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()

    if args.spec:
        # the spec file defines the experiment; a mesh-path flag alongside
        # it would be silently ignored, which the spec layer forbids
        # (same contract as simulate.py) -- only --rounds/--engine
        # override the file, plus --json/--checkpoint outputs
        ignored = [f"--{k.replace('_', '-')}"
                   for k in ("arch", "reduced", "devices", "mesh_shape",
                             "ens", "k0", "seq", "global_batch")
                   if getattr(args, k) != ap.get_default(k)]
        if ignored:
            ap.error(f"{', '.join(ignored)} cannot be combined with "
                     f"--spec (the file defines the experiment; only "
                     f"--rounds/--engine override it)")
        return run_spec(args)
    args.rounds = args.rounds_flag if args.rounds_flag is not None else 3

    if args.devices:
        os.environ["XLA_FLAGS"] = (
            f"--xla_force_host_platform_device_count={args.devices} "
            + os.environ.get("XLA_FLAGS", ""))

    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro import configs
    from repro.launch import steps as steps_mod
    from repro.launch.mesh import make_mesh, make_production_mesh

    if args.mesh_shape:
        dd, mm = (int(x) for x in args.mesh_shape.split(","))
        mesh = make_mesh((dd, mm), ("data", "model"))
    else:
        mesh = make_production_mesh()
    print(f"mesh: {dict(mesh.shape)}  devices: {len(jax.devices())}")

    if args.seq or args.global_batch:
        import dataclasses as _dc

        from repro.models.config import INPUT_SHAPES
        base = INPUT_SHAPES["train_4k"]
        INPUT_SHAPES["train_4k"] = _dc.replace(
            base, seq_len=args.seq or base.seq_len,
            global_batch=args.global_batch or base.global_batch)
    if args.reduced:
        real_get = configs.get_config
        configs.get_config = configs.get_reduced
    try:
        bundle = steps_mod.build_train_step(args.arch, mesh, ens=args.ens,
                                            k0=args.k0)
    finally:
        if args.reduced:
            configs.get_config = real_get
    if isinstance(bundle, steps_mod.Skip):
        print("SKIP:", bundle.reason)
        return 1
    cfg = bundle.static["cfg"]
    m = bundle.static["m"]
    b_local = bundle.static["b_local"]
    print(f"arch={cfg.name} fedepm[{bundle.static['mode']}] m={m} "
          f"b_local={b_local} seq={args.seq or 4096} k0={args.k0}")

    # real data + real init (the dry-run path uses ShapeDtypeStructs; the
    # launcher allocates)
    from repro.core import distributed as dist_mod
    from repro.core.fedepm import FedEPMConfig
    from repro.data.lm import federated_token_batches
    from repro.models.registry import get_model

    jitted = jax.jit(bundle.fn, in_shardings=bundle.in_shardings,
                     out_shardings=bundle.out_shardings,
                     donate_argnums=bundle.donate_argnums)

    model = get_model(cfg)
    fed_cfg = bundle.static["fed"]
    dist = dist_mod.DistConfig()  # only init_fn is needed here
    init_fn, _, _ = dist_mod.build_fedepm(model, lambda *a: 0.0, fed_cfg,
                                          mesh, dist)
    state = init_fn(jax.random.PRNGKey(0))

    seq = bundle.args[1]["tokens"].shape[-1] if "tokens" in bundle.args[1] \
        else bundle.args[1]["frame_embeds"].shape[-2]
    stream = federated_token_batches(cfg.vocab, m, b_local, seq,
                                     steps=args.rounds)
    import time
    for r, raw in enumerate(stream):
        batch = {}
        for k, spec in bundle.args[1].items():
            if k in raw:
                batch[k] = jnp.asarray(raw[k][..., :spec.shape[-1]])
            else:  # frontend stubs
                batch[k] = jnp.zeros(spec.shape, spec.dtype)
        if "targets" in bundle.args[1] and "targets" in raw:
            tgt_shape = bundle.args[1]["targets"].shape
            t = np.zeros(tgt_shape, np.int32)
            tt = raw["targets"][..., :tgt_shape[-1]]
            t[..., -tt.shape[-1]:] = tt
            batch["targets"] = jnp.asarray(t)
            lm_ = np.zeros(tgt_shape, np.float32)
            lm_[..., -tt.shape[-1]:] = 1.0
            batch["loss_mask"] = jnp.asarray(lm_)
        t0 = time.time()
        state, metrics = jitted(state, batch)
        jax.block_until_ready(metrics.drift)
        print(f"round {r}: drift={float(metrics.drift):.3e} "
              f"snr={float(metrics.snr):.2f} "
              f"sel={int(metrics.selected.sum())}/{m} "
              f"({time.time()-t0:.1f}s)")
    if args.checkpoint:
        from repro.checkpoint import save
        save(args.checkpoint, jax.device_get(state.w_tau),
             {"arch": cfg.name})
        print("saved", args.checkpoint)
    return 0


if __name__ == "__main__":
    sys.exit(main())
