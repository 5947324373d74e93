"""Serving launcher: prefill + decode loop on the mesh.

    PYTHONPATH=src python -m repro.launch.serve --arch zamba2-1.2b \
        --devices 8 --mesh-shape 4,2 --reduced --new-tokens 8
"""
import argparse
import os
import sys


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="smollm-135m")
    ap.add_argument("--reduced", action="store_true")
    ap.add_argument("--devices", type=int, default=0)
    ap.add_argument("--mesh-shape", default="")
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--prompt-len", type=int, default=64)
    ap.add_argument("--new-tokens", type=int, default=8)
    args = ap.parse_args(argv)

    if args.devices:
        os.environ["XLA_FLAGS"] = (
            f"--xla_force_host_platform_device_count={args.devices} "
            + os.environ.get("XLA_FLAGS", ""))

    import time

    import jax
    import jax.numpy as jnp

    from repro import configs
    from repro.core import distributed as dist_mod
    from repro.launch.mesh import make_mesh, make_production_mesh
    from repro.launch.steps import _named, serve_activation_rules
    from repro.models.registry import get_model
    from repro.sharding.rules import axis_rules

    if args.mesh_shape:
        dd, mm = (int(x) for x in args.mesh_shape.split(","))
        mesh = make_mesh((dd, mm), ("data", "model"))
    else:
        mesh = make_production_mesh()

    cfg = configs.get_reduced(args.arch) if args.reduced \
        else configs.get_config(args.arch)
    model = get_model(cfg)
    if not model.has_decode:
        print(f"{args.arch} is encoder-only; nothing to decode")
        return 1

    rules = serve_activation_rules(mesh)
    aparams = jax.eval_shape(lambda: model.init(jax.random.PRNGKey(0)))
    pspecs = dist_mod.param_specs(cfg, aparams, mesh, dist_mod.DistConfig())
    psh = _named(pspecs, mesh)
    params = jax.jit(lambda k: model.init(k), out_shardings=psh)(
        jax.random.PRNGKey(0))

    B, Tp = args.batch, args.prompt_len
    max_len = Tp + args.new_tokens + (cfg.n_patches or 0)

    def prefill_fn(p, b):
        with axis_rules(mesh, rules):
            return model.prefill(p, b, max_len=max_len)

    def decode_fn(p, st, b):
        with axis_rules(mesh, rules):
            return model.decode_step(p, st, b)

    prompts = jax.random.randint(jax.random.PRNGKey(1), (B, Tp), 0,
                                 cfg.vocab)
    batch = {"tokens": prompts}
    if cfg.family == "vlm":
        batch["patch_embeds"] = jax.random.normal(
            jax.random.PRNGKey(2), (B, cfg.n_patches, cfg.d_model),
            dtype=cfg.dtype)

    t0 = time.time()
    logits, state = jax.jit(prefill_fn)(params, batch)
    jax.block_until_ready(logits)
    print(f"prefill {Tp}x{B}: {time.time()-t0:.2f}s")

    # the decode state keeps whatever shardings prefill produced (the
    # dry-run path pins them via auto_state_specs; here the live arrays
    # already carry shardings, so let jit adopt them)
    decode = jax.jit(decode_fn, donate_argnums=1)
    tok = jnp.argmax(logits[:, -1], axis=-1)[:, None]
    t0 = time.time()
    for _ in range(args.new_tokens):
        logits, state = decode(params, state, {"tokens": tok})
        tok = jnp.argmax(logits[:, 0], axis=-1)[:, None]
    jax.block_until_ready(tok)
    dt = time.time() - t0
    print(f"decode {args.new_tokens} tokens: {dt:.2f}s "
          f"({args.new_tokens*B/dt:.1f} tok/s)")
    return 0


if __name__ == "__main__":
    sys.exit(main())
