"""ENS kernel validation: Pallas (interpret) and jnp ref vs brute-force
oracle, plus property-based tests of the Lemma III.1/III.2 solution."""
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

# hypothesis is optional: on a bare environment only the property-based
# tests skip; the kernel-vs-oracle validation still runs
try:
    import hypothesis
    import hypothesis.extra.numpy as hnp
    import hypothesis.strategies as st
except ImportError:
    hypothesis = None

from repro.kernels.ens import ops, ref

jax.config.update("jax_enable_x64", False)


@pytest.mark.parametrize("m", [1, 2, 3, 5, 8, 16, 33])
@pytest.mark.parametrize("n", [1, 7, 128, 513])
@pytest.mark.parametrize("lam_eta", [(0.5, 1.0), (1e-3, 2e-3), (2.0, 0.5)])
def test_ref_matches_oracle(m, n, lam_eta):
    lam, eta = lam_eta
    key = jax.random.PRNGKey(m * 1000 + n)
    Z = jax.random.normal(key, (m, n)) * 3.0
    w_ref = ref.ens_ref(Z, lam, eta)
    w_orc = ref.ens_oracle(Z, lam, eta)
    # near-ties can make the fp32 brute-force argmin pick the wrong knot;
    # the meaningful check is on the OBJECTIVE (in float64)
    Z64 = np.asarray(Z, np.float64)

    def obj(w):
        d = np.asarray(w, np.float64)[None, :] - Z64
        return np.sum(lam * np.abs(d) + eta / 2 * d * d, axis=0)

    assert np.all(obj(w_ref) <= obj(w_orc) + 1e-6 * (1 + np.abs(obj(w_orc))))


@pytest.mark.parametrize("m", [2, 4, 16, 50])
@pytest.mark.parametrize("n", [64, 500, 1024])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_pallas_matches_ref(m, n, dtype):
    lam, eta = 0.3, 0.9
    key = jax.random.PRNGKey(m + n)
    Z = (jax.random.normal(key, (m, n)) * 2.0).astype(dtype)
    w_pal = ops.ens(Z, lam, eta, impl="pallas", block_n=128, interpret=True)
    w_ref = ref.ens_ref(Z.astype(jnp.float32), lam, eta)
    atol = 1e-5 if dtype == jnp.float32 else 5e-2
    np.testing.assert_allclose(np.asarray(w_pal, np.float32), w_ref,
                               atol=atol, rtol=1e-2)


def test_objective_is_minimised_at_ens():
    """ENS output beats 1000 random perturbations on the true objective."""
    key = jax.random.PRNGKey(0)
    m, n = 9, 37
    lam, eta = 0.7, 1.3
    Z = jax.random.normal(key, (m, n)) * 2.0
    w = ref.ens_ref(Z, lam, eta)
    base = ref.ens_objective(Z, w, lam, eta)  # (n,)
    for i in range(20):
        pert = w + jax.random.normal(jax.random.fold_in(key, i), (n,)) * 0.1
        obj = ref.ens_objective(Z, pert, lam, eta)
        assert bool(jnp.all(obj >= base - 1e-5))


if hypothesis is not None:
    _given_properties = hypothesis.given(
        Z=hnp.arrays(np.float32, hnp.array_shapes(min_dims=2, max_dims=2,
                                                  min_side=1, max_side=24),
                     elements=st.floats(-50, 50, width=32)),
        lam=st.floats(1e-4, 5.0),
        ratio=st.floats(0.1, 10.0),
    )
    _settings_properties = hypothesis.settings(deadline=None, max_examples=40)
else:
    _given_properties = pytest.mark.skip(reason="hypothesis not installed")
    _settings_properties = lambda f: f  # noqa: E731


@_settings_properties
@_given_properties
def test_properties(Z, lam, ratio):
    eta = lam * ratio
    Z = jnp.asarray(Z)
    m, n = Z.shape
    w = ref.ens_ref(Z, lam, eta)
    # (1) bounded by the per-coordinate extremes of the candidate set
    lo = jnp.min(Z, axis=0) - lam / eta
    hi = jnp.max(Z, axis=0) + lam / eta
    assert bool(jnp.all(w >= lo - 1e-4)) and bool(jnp.all(w <= hi + 1e-4))
    # (2) translation equivariance
    w_shift = ref.ens_ref(Z + 5.0, lam, eta)
    np.testing.assert_allclose(w_shift, w + 5.0, atol=1e-4)
    # (3) permutation invariance over clients
    perm = np.random.RandomState(0).permutation(m)
    np.testing.assert_allclose(ref.ens_ref(Z[perm], lam, eta), w, atol=1e-5)
    # (4) idempotence: all clients equal => that value exactly
    Zc = jnp.broadcast_to(Z[:1], Z.shape)
    np.testing.assert_allclose(ref.ens_ref(Zc, lam, eta), Z[0], atol=1e-5)


def test_limits_mean_and_median():
    key = jax.random.PRNGKey(3)
    m, n = 11, 50
    Z = jax.random.normal(key, (m, n)) * 2.0
    # lam -> 0: ENS -> mean (FedAvg aggregation)
    w0 = ref.ens_ref(Z, 1e-9, 1.0)
    np.testing.assert_allclose(w0, jnp.mean(Z, axis=0), atol=1e-5)
    # eta -> 0 (lam/eta -> inf): ENS -> coordinate-wise median, eq. (5)
    w1 = ref.ens_ref(Z, 1.0, 1e-9)
    np.testing.assert_allclose(w1, jnp.median(Z, axis=0), atol=1e-4)


def test_subgradient_optimality():
    """Zero in the subdifferential at the ENS point (Lemma III.2)."""
    key = jax.random.PRNGKey(5)
    m, n = 13, 29
    lam, eta = 0.8, 1.7
    Z = jax.random.normal(key, (m, n)) * 2.0
    w = ref.ens_ref(Z, lam, eta)
    d = w[None, :] - Z                       # (m, n)
    g_smooth = eta * jnp.sum(d, axis=0)      # smooth part
    s_fixed = lam * jnp.sum(jnp.sign(jnp.where(jnp.abs(d) > 1e-6, d, 0.0)),
                            axis=0)
    slack = lam * jnp.sum((jnp.abs(d) <= 1e-6).astype(jnp.float32), axis=0)
    resid = jnp.maximum(jnp.abs(g_smooth + s_fixed) - slack, 0.0)
    assert float(jnp.max(resid)) < 1e-3


def test_paper_algorithm_documented_deviation():
    """The literal Algorithm 1 (ens_paper) disagrees with the true argmin
    in asymmetric cases -- the sign issue documented in kernels/ens/ref.py.
    We assert the *oracle-correct* implementation wins on the objective."""
    Z = jnp.asarray([[0.0, 10.0], [1.0, 12.0], [5.0, 13.0]])
    lam, eta = 1.0, 0.5
    w_paper = ref.ens_paper(Z, lam, eta)
    w_ref = ref.ens_ref(Z, lam, eta)
    obj_p = ref.ens_objective(Z, w_paper, lam, eta)
    obj_r = ref.ens_objective(Z, w_ref, lam, eta)
    assert bool(jnp.all(obj_r <= obj_p + 1e-6))


def test_ens_tree_shapes():
    key = jax.random.PRNGKey(1)
    tree = {"a": jax.random.normal(key, (5, 3, 4)),
            "b": [jax.random.normal(key, (5, 7))]}
    out = ops.ens_tree(tree, 0.1, 0.2, impl="ref")
    assert out["a"].shape == (3, 4)
    assert out["b"][0].shape == (7,)


def _leaf(kind: str, shape: tuple, seed: int) -> np.ndarray:
    rng = np.random.default_rng(seed)
    if kind == "gauss":
        return (rng.standard_normal(shape) * 3.0).astype(np.float32)
    if kind == "ties":
        return rng.integers(-2, 3, shape).astype(np.float32)
    return rng.choice(np.array([0.0, -0.0, 1.0, -1.0], np.float32), shape)


@pytest.mark.parametrize("m", range(1, 9))
@pytest.mark.parametrize("trailing", [(7,), (3, 1, 5), (2, 1, 3, 4)],
                         ids=["1d", "3d-size1-axis1", "4d"])
@pytest.mark.parametrize("lam_eta", [(0.5, 1.0), (1e-9, 1.0), (1.0, 1e-9)],
                         ids=["mid", "lam0", "eta0"])
@pytest.mark.parametrize("kind", ["gauss", "ties", "signed-zeros"])
def test_select_equals_sort(m, trailing, lam_eta, kind):
    """The merge-select identity returns the value the sort puts in the
    middle, exactly (assert_array_equal counts -0.0 equal to 0.0)."""
    lam, eta = lam_eta
    Z = jnp.asarray(_leaf(kind, (m,) + trailing, seed=m * 31 + len(trailing)))
    np.testing.assert_array_equal(ref.ens_select(Z, lam, eta),
                                  ref.ens_ref(Z, lam, eta))


@pytest.mark.parametrize("m", [2, 4, 8, 50])
def test_ens_tree_above_chunk_threshold_equals_leafwise_sort(monkeypatch, m):
    """Leaves above ``_CHUNK_THRESHOLD``: selected whole (m <= 8), the same
    floats as one sort per leaf; or sorted in lax.map chunks (m = 50),
    whose per-chunk mean may sum in another order (last ulp)."""
    monkeypatch.setattr(ops, "_CHUNK_THRESHOLD", 64)
    rng = np.random.default_rng(m)
    tree = {"big": jnp.asarray(rng.standard_normal((m, 6, 5, 4)), jnp.float32),
            "emb": jnp.asarray(_leaf("ties", (m, 40, 3), m)),
            "small": jnp.asarray(rng.standard_normal((m, 3)), jnp.float32)}
    out = ops.ens_tree(tree, 0.3, 0.9, impl="ref")
    for k, z in tree.items():
        if m <= ops._SELECT_MAX_M:
            np.testing.assert_array_equal(out[k], ref.ens_ref(z, 0.3, 0.9))
        else:
            np.testing.assert_allclose(out[k], ref.ens_ref(z, 0.3, 0.9),
                                       rtol=1e-6, atol=1e-7)


@pytest.mark.parametrize("m,sorts", [(2, False), (4, False), (8, False),
                                     (50, True)])
def test_ens_tree_sorts_only_above_the_selection_bound(m, sorts):
    tree = {"a": jnp.zeros((m, 7, 3)), "b": jnp.zeros((m, 11))}
    text = jax.jit(lambda t: ops.ens_tree(t, 0.5, 1.0)).lower(
        tree).compile().as_text()
    assert (re.search(r"\bsort\(", text) is not None) == sorts
    assert ("/ens_select/" in text) != sorts


@pytest.mark.parametrize("lam,eta,selects", [
    (0.5, 1.0, True), (0.0, 1.0, True), (np.float32(0.5), 1.0, True),
    (-0.5, 1.0, False), (0.5, jnp.float32(1.0), False)],
    ids=["positive", "lam0", "numpy-scalar", "negative", "array"])
def test_selection_needs_a_nonnegative_ratio_known_at_trace_time(
        lam, eta, selects):
    assert ops._selects(4, lam, eta) == selects
    assert not ops._selects(ops._SELECT_MAX_M + 1, 0.5, 1.0)


@pytest.mark.parametrize("m", [1, 3, 8, 9])
def test_ens_ref_impl_follows_the_path_rule(m):
    Z = jnp.asarray(_leaf("ties", (m, 33), m))
    np.testing.assert_array_equal(ops.ens(Z, 0.25, 0.5, impl="ref"),
                                  ref.ens_ref(Z, 0.25, 0.5))


@pytest.mark.parametrize("m", range(1, 9))
@pytest.mark.parametrize("lam_eta", [(0.5, 1.0), (1.0, 1e-9)],
                         ids=["mid", "eta0"])
def test_select_equals_sort_in_bfloat16(m, lam_eta):
    """A bfloat16 leaf: the selection returns the sort's floats, in the
    leaf's dtype."""
    lam, eta = lam_eta
    Z = jnp.asarray(_leaf("gauss", (m, 6, 35), m)).astype(jnp.bfloat16)
    got, want = ref.ens_select(Z, lam, eta), ref.ens_ref(Z, lam, eta)
    assert got.dtype == want.dtype == jnp.bfloat16
    np.testing.assert_array_equal(np.asarray(got, np.float32),
                                  np.asarray(want, np.float32))


@pytest.mark.parametrize("m", [1, 2, 4, 8])
@pytest.mark.parametrize("fn,stacks", [("ens_ref", True),
                                       ("ens_select", False)])
def test_selection_stacks_no_rows(m, fn, stacks):
    """The sort stacks the 2m+1 rows and sorts them; the selection builds
    no stack and no sort, only elementwise ops past the mean."""
    f = getattr(ref, fn)
    text = jax.jit(lambda z: f(z, 0.5, 1.0)).lower(
        jnp.zeros((m, 5, 3))).as_text()
    assert ("stablehlo.concatenate" in text) == stacks
    assert ("stablehlo.sort" in text) == stacks


@pytest.mark.parametrize("case", ["paper-logreg", "stack-m8"])
def test_ens_paths_script_tiny(case):
    """``benchmarks/ens_paths.py`` at its CPU size: a row per lam/eta with
    both paths timed; up to the selection bound the two agree."""
    from benchmarks import ens_paths
    name, m, shapes = next(c for c in ens_paths.cases(tiny=True)
                           if c[0] == case)
    rows = ens_paths.run_case(name, m, shapes, reps=1)
    assert [r["lam_over_eta"] for r in rows] == list(ens_paths.RATIOS)
    for r in rows:
        assert r["select_ms"] > 0 and r["sort_ms"] > 0
        assert r["elements"] == sum(int(np.prod(s)) for s in shapes)
        if m <= ops._SELECT_MAX_M:
            assert r["unequal"] == 0
