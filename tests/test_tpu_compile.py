"""The six Pallas kernels compile for a TPU v5e chip that is described,
not attached.

The TPU compiler is installed with jaxlib, so each kernel is lowered with
``interpret=False`` for one described v5e device and compiled; the
compiled text must hold the kernel as a ``tpu_custom_call``. Shapes are
the ones ``chip_smoke.py`` runs: the paper task (m=50 clients, n=14
features) and the LM task (m=4 clients, smollm-135m's 49152x576
embedding, its widest leaf).

The topology is described inside a fixture, never at import: only one
process may load the TPU library, and under pytest-xdist every worker
imports this file. The persistent compile cache is off around each
compile (an entry written for a described chip cannot be read back).
"""
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels.ens.ens import ens_pallas
from repro.kernels.prox.prox import prox_update_pallas
from repro.kernels.quant.batch import quantize_cols_pallas
from repro.kernels.quant.ef import ef_accumulate_pallas
from repro.kernels.quant.privacy import private_quantize_cols_pallas
from repro.kernels.quant.quant import quantize_pallas

# (clients m, per-client width n): paper logreg; smollm-135m embedding
SHAPES = {"paper_m50": (50, 14), "lm_m4": (4, 49152 * 576)}
BITS = 8


@pytest.fixture(scope="module")
def one_chip():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture
def no_persistent_cache():
    from jax.experimental.compilation_cache import compilation_cache as cc
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", was)
    cc.reset_cache()


def _kernel_case(name, m, n):
    """(fn, arg (shape, dtype) list) for one kernel at (m, n)."""
    f32, u32, i32 = jnp.float32, jnp.uint32, jnp.int32
    mat, row = ((m, n), f32), ((m,), f32)
    if name == "ens":
        return (lambda Z: ens_pallas(Z, 1e-5, 2e-5, interpret=False),
                [mat])
    if name == "prox":
        # the FedEPM round runs it vmapped over the client axis
        def prox(W, w, G):
            return jax.vmap(lambda wi, gi: prox_update_pallas(
                wi, w, gi, 0.05, 1e-5, 2e-5, interpret=False))(W, G)
        return prox, [mat, ((n,), f32), mat]
    if name == "quant":
        return (lambda X, s, u: quantize_pallas(X, s, BITS, u,
                                                interpret=False),
                [mat, row, ((m, n), u32)])
    if name == "ef_accumulate":
        return (lambda Z, H, s, u: ef_accumulate_pallas(
                    Z, H, s, BITS, u, interpret=False),
                [mat, mat, row, ((m, n), u32)])
    if name == "quantize_cols":
        return (lambda X, F, s, k, u: quantize_cols_pallas(
                    X, F, s, k, BITS, u, interpret=False),
                [mat, mat, row, ((m,), i32), ((m, n), u32)])
    if name == "private_quantize_cols":
        return (lambda X, F, cf, b, s, k, u, lap:
                private_quantize_cols_pallas(X, F, cf, b, s, k, BITS, u,
                                             lap, interpret=False),
                [mat, mat, row, row, row, ((m,), i32), ((m, n), u32),
                 mat])
    raise ValueError(name)


@pytest.mark.parametrize("shape", sorted(SHAPES))
@pytest.mark.parametrize("kernel", ["ens", "prox", "quant", "ef_accumulate",
                                    "quantize_cols",
                                    "private_quantize_cols"])
def test_kernel_compiles_for_v5e(kernel, shape, one_chip,
                                 no_persistent_cache):
    fn, args = _kernel_case(kernel, *SHAPES[shape])
    specs = [jax.ShapeDtypeStruct(s, dt, sharding=one_chip)
             for s, dt in args]
    compiled = jax.jit(fn).lower(*specs).compile()
    assert "tpu_custom_call" in compiled.as_text()
