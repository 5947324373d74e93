"""ENS's two jnp paths at the shapes the benchmark cells aggregate: the
min/max selection (``ref.ens_select``) against the sort
(``ops._ens_ref_chunked``), each compiled as a program of its own.

For each case it draws an N(0, 0.02) client state at the case's leaf
shapes and reports, per lam/eta ratio, the median wall time of a call of
each path and how many coordinates the two give differently (and how many
of those differ only in the sign of a zero). The cases:

* ``smollm-135m`` (m = 4) and ``mellum2-12b-a2.5b`` (m = 2): every leaf
  of the model state, as ``ens_tree`` sees it in those cells;
* ``paper-logreg`` (m = 50): the (50, 14) weight the logreg cell
  aggregates;
* ``stack-m<m>``: one layer-stacked leaf (m, 30, 2^15) for m = 8, 16, 50,
  which shows how the selection's network and the sort scale in m.

On a TPU ENS is fused into the round's other passes, so its device time
in a round has no scope of its own to read (``PERF.md`` section 3); this
script times it alone. Run from the repo root::

    PYTHONPATH=src python3 benchmarks/ens_paths.py [--out FILE] [--tiny]

``--tiny`` takes the reduced model configurations and small stacks, for a
check on the CPU. Each result is printed and, with ``--out``, appended to
FILE as one JSON line.
"""
from __future__ import annotations

import argparse
import json
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro import configs
from repro.kernels.ens import ops, ref
from repro.models import registry as model_registry

RATIOS = (0.5, 1e-6)   # lam/eta: candidates spread, and all on the mean


def _model_shapes(arch: str, tiny: bool) -> list[tuple[int, ...]]:
    cfg = (configs.get_reduced if tiny else configs.get_config)(arch)
    shapes = jax.eval_shape(model_registry.get_model(cfg).init,
                            jax.random.PRNGKey(0))
    return [s.shape for s in jax.tree_util.tree_leaves(shapes)]


def cases(tiny: bool = False) -> list[tuple[str, int, list]]:
    """-> [(name, m, leaf shapes without the client axis)]."""
    stack = (30, 1 << (6 if tiny else 15))
    return ([("smollm-135m", 4, _model_shapes("smollm-135m", tiny)),
             ("mellum2-12b-a2.5b", 2,
              _model_shapes("mellum2-12b-a2.5b", tiny)),
             ("paper-logreg", 50, [(14,)])]
            + [(f"stack-m{m}", m, [stack]) for m in (8, 16, 50)])


def _median_ms(f, Z, reps: int) -> float:
    jax.block_until_ready(f(Z))
    ts = []
    for _ in range(reps):
        t = time.perf_counter()
        jax.block_until_ready(f(Z))
        ts.append(time.perf_counter() - t)
    return 1e3 * float(np.median(ts))


@jax.jit
def _unequal(a, b):
    n = sum(jnp.sum(x != y) for x, y in zip(a, b))
    s = sum(jnp.sum((x == y) & (jnp.signbit(x) != jnp.signbit(y)))
            for x, y in zip(a, b))
    return n, s


def run_case(name: str, m: int, shapes: list, *, reps: int = 5,
             seed: int = 7) -> list[dict]:
    key = jax.random.PRNGKey(seed)
    Z = [jax.random.normal(jax.random.fold_in(key, i), (m,) + s,
                           jnp.float32) * 0.02 for i, s in enumerate(shapes)]
    dev = jax.devices()[0]
    rows = []
    for ratio in RATIOS:
        lam, eta = ratio * 1e-5, 1e-5
        f_sel = jax.jit(lambda z: [ref.ens_select(l, lam, eta) for l in z])
        f_sort = jax.jit(
            lambda z: [ops._ens_ref_chunked(l, lam, eta) for l in z])
        row = {"case": name, "m": m, "lam_over_eta": ratio,
               "elements": int(sum(np.prod(s) for s in shapes)),
               "select_ms": _median_ms(f_sel, Z, reps),
               "sort_ms": _median_ms(f_sort, Z, reps)}
        ne, ns = _unequal(f_sel(Z), f_sort(Z))
        row.update(unequal=int(ne), zero_sign_only=int(ns),
                   device=f"{dev.platform}:{dev.device_kind}")
        rows.append(row)
    return rows


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--tiny", action="store_true",
                    help="reduced configurations and small stacks (CPU)")
    ap.add_argument("--only", default=None,
                    help="comma-separated case names")
    ap.add_argument("--reps", type=int, default=5)
    ap.add_argument("--out", default=None, help="append JSON lines here")
    args = ap.parse_args(argv)
    keep = set(args.only.split(",")) if args.only else None
    for name, m, shapes in cases(args.tiny):
        if keep is not None and name not in keep:
            continue
        for row in run_case(name, m, shapes, reps=args.reps):
            print(json.dumps(row), flush=True)
            if args.out:
                with open(args.out, "a") as f:
                    f.write(json.dumps(row) + "\n")
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
