"""ENS kernel micro-benchmark: jnp reference (XLA sort) vs the literal
paper Algorithm 1 vs the Pallas kernel. The kernel runs compiled on a TPU
and interpreted anywhere else (``default_interpret``); every row names
the device it ran on, so an interpreted timing never passes for a chip
one."""
from __future__ import annotations

import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.kernels.common import default_interpret
from repro.kernels.ens import ops, ref


def _time(fn, *args, reps=10):
    out = fn(*args)
    jax.block_until_ready(out)
    t0 = time.perf_counter()
    for _ in range(reps):
        out = fn(*args)
    jax.block_until_ready(out)
    return (time.perf_counter() - t0) / reps


def run(m=32, n=1 << 16, lam=0.5, eta=1.0):
    key = jax.random.PRNGKey(0)
    Z = jax.random.normal(key, (m, n))
    dev = jax.devices()[0]
    where = f"{dev.platform}:{dev.device_kind}"
    rows = []
    f_ref = jax.jit(lambda z: ref.ens_ref(z, lam, eta))
    f_pap = jax.jit(lambda z: ref.ens_paper(z, lam, eta))
    t_ref = _time(f_ref, Z)
    t_pap = _time(f_pap, Z)
    rows.append((f"ens/ref_m{m}_n{n}", t_ref * 1e6,
                 f"median-identity;device={where}"))
    rows.append((f"ens/paper_alg1_m{m}_n{n}", t_pap * 1e6,
                 f"literal Algorithm 1;device={where}"))
    interpret = default_interpret()
    mode = "interpret" if interpret else "compiled"
    f_pal = jax.jit(lambda z: ops.ens(z, lam, eta, impl="pallas",
                                      interpret=interpret))
    t_pal = _time(f_pal, Z)
    w_ref = f_ref(Z)
    err = float(jnp.max(jnp.abs(f_pal(Z) - w_ref)))
    rows.append((f"ens/pallas_{mode}_m{m}_n{n}", t_pal * 1e6,
                 f"maxerr={err:.2e};device={where}"))
    # objective comparison ref vs paper algorithm (documented deviation)
    obj_ref = float(jnp.sum(ref.ens_objective(Z, w_ref, lam, eta)))
    w_pap_v = f_pap(Z)
    obj_pap = float(jnp.sum(ref.ens_objective(Z, w_pap_v, lam, eta)))
    rows.append(("ens/objective_ref_vs_paper", 0.0,
                 f"ref={obj_ref:.4f};paper={obj_pap:.4f};"
                 f"ref_leq={obj_ref <= obj_pap + 1e-3}"))
    return rows


if __name__ == "__main__":
    for r in run():
        print(",".join(map(str, r)))
