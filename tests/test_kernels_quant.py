"""Upload-codec quantizer + error-feedback accumulate: Pallas (interpret)
vs jnp ref, grid/unbiasedness properties, property-based (hypothesis)
codec laws, and the transport codec round-trip built on top of them."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

# hypothesis is optional: on a bare environment only the property-based
# tests skip; the kernel validation still runs
try:
    import hypothesis
    import hypothesis.strategies as st
except ImportError:
    hypothesis = None

from repro.kernels.quant import ops, ref
from repro.sim.transport import CodecConfig, codec_roundtrip, encoded_client_bytes


def _data(m, n, seed=0, scale=2.0):
    key = jax.random.PRNGKey(seed)
    X = jax.random.normal(key, (m, n)) * scale
    s = jnp.max(jnp.abs(X), axis=1)
    u32 = jax.random.bits(jax.random.fold_in(key, 1), (m, n),
                          dtype=jnp.uint32)
    return X, s, u32


@pytest.mark.parametrize("m,n", [(1, 7), (5, 300), (32, 1024), (3, 513)])
@pytest.mark.parametrize("bits", [2, 4, 8, 16])
@pytest.mark.parametrize("stochastic", [True, False])
def test_pallas_matches_ref_bitexact(m, n, bits, stochastic):
    """Same dither bits => the kernel and the jnp reference must agree
    EXACTLY (the dither is an input, not drawn in-kernel)."""
    X, s, u32 = _data(m, n, seed=m * n)
    u = u32 if stochastic else None
    qp = ops.quantize(X, s, bits, u, impl="pallas", interpret=True)
    qr = ops.quantize(X, s, bits, u, impl="ref")
    assert np.array_equal(np.asarray(qp), np.asarray(qr))
    assert qp.dtype == X.dtype


@pytest.mark.parametrize("jit", [False, True])
def test_u32_to_f32_matches_astype_bitexact(jit):
    """The kernels' Mosaic-safe uint32 -> float32 conversion rounds exactly
    like the direct cast: random words plus the rounding edges."""
    from repro.kernels.common import u32_to_f32
    edges = np.array([0, 1, 2**16 - 1, 2**16, 2**24 - 1, 2**24, 2**24 + 1,
                      2**24 + 3, 2**31 - 1, 2**31, 2**31 + 1, 2**32 - 129,
                      2**32 - 128, 2**32 - 127, 2**32 - 1], np.uint32)
    u = jnp.concatenate([jax.random.bits(jax.random.PRNGKey(3), (1 << 20,),
                                         dtype=jnp.uint32), edges])
    conv = jax.jit(u32_to_f32) if jit else u32_to_f32
    got = np.asarray(conv(u)).view(np.uint32)
    want = np.asarray(u.astype(jnp.float32)).view(np.uint32)
    assert np.array_equal(got, want)


@pytest.mark.parametrize("bits", [2, 4, 8])
def test_quantization_error_bounded(bits):
    """|q - x| <= delta (stochastic) resp. delta/2 (deterministic)."""
    X, s, u32 = _data(8, 400, seed=3)
    L = ref.quant_levels(bits)
    delta = np.asarray(s)[:, None] / L
    q_st = np.asarray(ops.quantize(X, s, bits, u32, impl="ref"))
    q_dt = np.asarray(ops.quantize(X, s, bits, None, impl="ref"))
    Xn = np.asarray(X)
    assert (np.abs(q_st - Xn) <= delta * (1 + 1e-6)).all()
    assert (np.abs(q_dt - Xn) <= delta / 2 + delta * 1e-6).all()


def test_values_on_grid():
    X, s, u32 = _data(4, 200, seed=5)
    bits = 4
    L = ref.quant_levels(bits)
    q = np.asarray(ops.quantize(X, s, bits, u32, impl="ref"), np.float64)
    delta = (np.asarray(s, np.float64) * np.float32(1.0 / L))[:, None]
    levels = np.rint(q / delta)
    np.testing.assert_allclose(levels * delta, q, rtol=1e-6)
    assert (np.abs(levels) <= L).all()


def test_stochastic_rounding_unbiased():
    """E[q] = x for |x| <= scale: average over many dither draws."""
    n = 4096
    X = jnp.full((1, n), 0.37, jnp.float32)
    s = jnp.ones((1,))
    means = []
    for seed in range(40):
        u32 = jax.random.bits(jax.random.PRNGKey(seed), (1, n),
                              dtype=jnp.uint32)
        means.append(float(np.asarray(
            ops.quantize(X, s, 4, u32, impl="ref")).mean()))
    assert abs(np.mean(means) - 0.37) < 2e-3
    # deterministic rounding is biased toward the nearer grid point instead
    q_dt = float(np.asarray(ops.quantize(X, s, 4, None, impl="ref")).mean())
    assert abs(q_dt - 0.37) > 5e-3


def test_zero_rows_quantize_to_zero():
    X, _, u32 = _data(4, 64, seed=7)
    X = X.at[2].set(0.0)
    s = jnp.max(jnp.abs(X), axis=1)
    for impl in ("ref", "pallas"):
        q = np.asarray(ops.quantize(X, s, 8, u32, impl=impl,
                                    interpret=True))
        assert (q[2] == 0).all()
        assert np.isfinite(q).all()


def test_bits_validation():
    X, s, _ = _data(2, 16)
    with pytest.raises(ValueError):
        ops.quantize(X, s, 1, None, impl="ref")


# ---------------------------------------------------------------------------
# error-feedback accumulate/compress (H + Q(Z - H))
# ---------------------------------------------------------------------------

def _ef_data(m, n, seed=0):
    key = jax.random.PRNGKey(seed)
    Z = jax.random.normal(key, (m, n)) * 2.0
    H = jax.random.normal(jax.random.fold_in(key, 1), (m, n))
    s = jnp.max(jnp.abs(Z - H), axis=1)
    u32 = jax.random.bits(jax.random.fold_in(key, 2), (m, n),
                          dtype=jnp.uint32)
    return Z, H, s, u32


@pytest.mark.parametrize("m,n", [(1, 7), (5, 300), (32, 1024), (3, 513)])
@pytest.mark.parametrize("bits", [2, 4, 8])
@pytest.mark.parametrize("stochastic", [True, False])
def test_ef_pallas_matches_ref_bitexact(m, n, bits, stochastic):
    """Fused kernel and jnp reference consume the same dither and must
    agree EXACTLY -- the codec-memory contract of docs/kernels.md."""
    Z, H, s, u32 = _ef_data(m, n, seed=m * n)
    u = u32 if stochastic else None
    op = ops.ef_accumulate(Z, H, s, bits, u, impl="pallas", interpret=True)
    orf = ops.ef_accumulate(Z, H, s, bits, u, impl="ref")
    assert np.array_equal(np.asarray(op), np.asarray(orf))
    assert op.dtype == Z.dtype


@pytest.mark.parametrize("bits", [4, 8])
def test_ef_accumulate_equals_quantized_residual(bits):
    """ef_accumulate(Z, H) == H + quantize(Z - H) up to the final-add
    rounding: the fused op keeps the accumulate in one FMA (one rounding),
    the composition rounds the dequantized residual to f32 first. The two
    therefore differ by at most 1 ulp of the DEQUANTIZED RESIDUAL (which,
    under cancellation h ~ -dec, can be many ulps of the tiny sum)."""
    Z, H, s, u32 = _ef_data(6, 256, seed=11)
    fused = np.asarray(ops.ef_accumulate(Z, H, s, bits, u32, impl="ref"))
    dec = np.asarray(ops.quantize(Z - H, s, bits, u32, impl="ref"))
    composed = np.asarray(H) + dec
    tol = np.spacing(np.maximum(np.abs(composed), np.abs(dec))
                     .astype(np.float32))
    assert (np.abs(fused - composed) <= tol).all()


def test_ef_zero_residual_rows_pass_h_through():
    """A row where Z == H (scale 0) must return H exactly -- a converged
    client's memory never drifts."""
    Z, H, _, u32 = _ef_data(4, 64, seed=5)
    Z = Z.at[2].set(H[2])
    s = jnp.max(jnp.abs(Z - H), axis=1)
    for impl in ("ref", "pallas"):
        out = np.asarray(ops.ef_accumulate(Z, H, s, 8, u32, impl=impl,
                                           interpret=True))
        np.testing.assert_array_equal(out[2], np.asarray(H)[2])
        assert np.isfinite(out).all()


def test_ef_error_bounded_by_residual_grid():
    """|out - Z| <= residual grid step: the memory moves to within one
    quantization step of the target."""
    Z, H, s, u32 = _ef_data(8, 400, seed=3)
    bits = 8
    L = ref.quant_levels(bits)
    delta = np.asarray(s)[:, None] / L
    out = np.asarray(ops.ef_accumulate(Z, H, s, bits, u32, impl="ref"))
    assert (np.abs(out - np.asarray(Z)) <= delta * (1 + 1e-6)).all()


def test_ef_shape_validation():
    Z, H, s, _ = _ef_data(2, 16)
    from repro.kernels.quant.ef import ef_accumulate_pallas
    with pytest.raises(ValueError, match="matching"):
        ef_accumulate_pallas(Z, H[:1], s, 8)


# ---------------------------------------------------------------------------
# property-based codec laws (hypothesis; optional as in the other kernels)
# ---------------------------------------------------------------------------

if hypothesis is not None:
    _given_codec_case = hypothesis.given(case=st.tuples(
        st.integers(1, 5),                       # m clients
        st.integers(2, 96),                      # n coords
        st.sampled_from([2, 4, 8]),              # wire bits
        st.floats(0.1, 1.0),                     # topk fraction
        st.integers(0, 2 ** 31 - 1),             # data seed
    ))
    _settings_codec = hypothesis.settings(deadline=None, max_examples=30)
else:
    _given_codec_case = pytest.mark.skip(reason="hypothesis not installed")
    _settings_codec = lambda f: f  # noqa: E731


def _rand_tree(m, n, seed, scale=3.0):
    key = jax.random.PRNGKey(seed % (2 ** 31 - 1))
    return {"w": jax.random.normal(key, (m, n)) * scale}


@_settings_codec
@_given_codec_case
def test_prop_roundtrip_error_bound(case):
    """|decode(encode(z)) - z| <= scale/levels on every KEPT coordinate,
    for any shape/bits/sparsity; dropped coordinates take the fallback
    exactly (here: z itself, isolating quantization error)."""
    m, n, bits, frac, seed = case
    t = _rand_tree(m, n, seed)
    out = codec_roundtrip(t, t, jax.random.PRNGKey(seed % 997),
                          CodecConfig(topk_frac=frac, bits=bits))
    L = ref.quant_levels(bits)
    z = np.asarray(t["w"], np.float64)
    o = np.asarray(out["w"], np.float64)
    k = n if frac >= 1.0 else max(1, int(np.ceil(frac * n)))
    for i in range(m):
        kept = np.argsort(-np.abs(z[i]))[:k]
        delta = np.abs(z[i, kept]).max() / L
        assert (np.abs(o[i, kept] - z[i, kept]) <= delta * (1 + 1e-5)).all()
        dropped = np.setdiff1d(np.arange(n), kept)
        np.testing.assert_array_equal(o[i, dropped], z[i, dropped])


@_settings_codec
@_given_codec_case
def test_prop_ef_residual_never_grows(case):
    """EF memory contraction, worst case: one deterministic-rounding EF
    pass never increases the residual sup-norm ||z - h|| -- kept
    coordinates land within half a grid step of their target, dropped
    coordinates keep their old (smaller-magnitude) residual."""
    from repro.sim.transport import ef_roundtrip

    m, n, bits, frac, seed = case
    z = _rand_tree(m, n, seed)
    h = _rand_tree(m, n, seed + 1, scale=1.0)
    codec = CodecConfig(topk_frac=frac, bits=bits, stochastic=False,
                        error_feedback=True)
    h_new = ef_roundtrip(z, h, jax.random.PRNGKey(0), codec)
    r0 = np.abs(np.asarray(z["w"], np.float64)
                - np.asarray(h["w"], np.float64)).max(axis=1)
    r1 = np.abs(np.asarray(z["w"], np.float64)
                - np.asarray(h_new["w"], np.float64)).max(axis=1)
    assert (r1 <= r0 * (1 + 1e-6)).all()


def test_prop_ef_residual_contracts_in_expectation():
    """Stochastic rounding can grow a single residual; ITS EXPECTATION must
    still contract: averaged over many dither draws, E||z - h'||^2 after
    one dense 8-bit EF pass is far below ||z - h||^2."""
    from repro.sim.transport import ef_roundtrip

    z = _rand_tree(4, 64, seed=0)
    h = _rand_tree(4, 64, seed=1, scale=1.0)
    codec = CodecConfig(topk_frac=1.0, bits=8, error_feedback=True)
    r0 = float(np.sum((np.asarray(z["w"]) - np.asarray(h["w"])) ** 2))
    sq = []
    for s in range(32):
        h_new = ef_roundtrip(z, h, jax.random.PRNGKey(s), codec)
        sq.append(float(np.sum(
            (np.asarray(z["w"]) - np.asarray(h_new["w"])) ** 2)))
    assert np.mean(sq) < 0.1 * r0


@_settings_codec
@_given_codec_case
def test_prop_topk_sparsity_count_exact(case):
    """The codec touches EXACTLY ceil(frac * n) coordinates per client per
    leaf -- the count the byte ledger bills for. A sentinel fallback makes
    touched coordinates identifiable."""
    m, n, bits, frac, seed = case
    t = _rand_tree(m, n, seed)          # |values| <= ~15, sentinel unreachable
    sentinel = 1.0e9
    fb = jax.tree_util.tree_map(lambda x: jnp.full_like(x, sentinel), t)
    out = codec_roundtrip(t, fb, jax.random.PRNGKey(seed % 997),
                          CodecConfig(topk_frac=frac, bits=bits))
    k = n if frac >= 1.0 else max(1, int(np.ceil(frac * n)))
    o = np.asarray(out["w"])
    touched = (o != sentinel).sum(axis=1)
    np.testing.assert_array_equal(touched, np.full(m, k))


# ---------------------------------------------------------------------------
# transport codec round-trip (top-k + quantize + dequantize-with-fallback)
# ---------------------------------------------------------------------------

def _tree(m, seed=0):
    key = jax.random.PRNGKey(seed)
    return {"w": jax.random.normal(key, (m, 6, 8)),
            "b": jax.random.normal(jax.random.fold_in(key, 1), (m, 10))}


def test_codec_identity_when_disabled():
    t = _tree(4)
    out = codec_roundtrip(t, t, jax.random.PRNGKey(0), None)
    assert out is t


def test_codec_dense_lossless_when_raw():
    """topk_frac=1, bits=0: the codec transmits everything exactly."""
    t = _tree(4)
    fb = jax.tree_util.tree_map(jnp.zeros_like, t)
    out = codec_roundtrip(t, fb, jax.random.PRNGKey(0),
                          CodecConfig(topk_frac=1.0, bits=0))
    for a, b in zip(jax.tree_util.tree_leaves(out),
                    jax.tree_util.tree_leaves(t)):
        assert np.array_equal(np.asarray(a), np.asarray(b))


def test_codec_topk_exact_on_kept_raw():
    """bits=0, topk<1: kept (top-magnitude) coords come through exactly,
    dropped coords take the fallback value."""
    m = 3
    t = _tree(m, seed=2)
    fb = jax.tree_util.tree_map(lambda x: jnp.full_like(x, -7.0), t)
    frac = 0.25
    out = codec_roundtrip(t, fb, jax.random.PRNGKey(0),
                          CodecConfig(topk_frac=frac, bits=0))
    for o, z in zip(jax.tree_util.tree_leaves(out),
                    jax.tree_util.tree_leaves(t)):
        of = np.asarray(o).reshape(m, -1)
        zf = np.asarray(z).reshape(m, -1)
        n = zf.shape[1]
        k = max(1, int(np.ceil(frac * n)))
        for i in range(m):
            kept = np.argsort(-np.abs(zf[i]))[:k]
            np.testing.assert_array_equal(of[i, kept], zf[i, kept])
            dropped = np.setdiff1d(np.arange(n), kept)
            assert (of[i, dropped] == -7.0).all()


def test_codec_quantized_close_and_on_grid():
    m = 4
    t = _tree(m, seed=3)
    fb = jax.tree_util.tree_map(jnp.zeros_like, t)
    out = codec_roundtrip(t, fb, jax.random.PRNGKey(1),
                          CodecConfig(topk_frac=1.0, bits=8))
    L = ref.quant_levels(8)
    for o, z in zip(jax.tree_util.tree_leaves(out),
                    jax.tree_util.tree_leaves(t)):
        of, zf = np.asarray(o).reshape(m, -1), np.asarray(z).reshape(m, -1)
        delta = np.abs(zf).max(axis=1, keepdims=True) / L
        assert (np.abs(of - zf) <= delta * (1 + 1e-5)).all()


def test_encoded_bytes_accounting():
    m = 2
    t = {"w": jnp.zeros((m, 100), jnp.float32)}
    # raw dense = 400 B
    assert encoded_client_bytes(t, None) == 400.0
    # dense 8-bit: 100 B payload + 4 B scale
    assert encoded_client_bytes(t, CodecConfig(topk_frac=1.0, bits=8)) \
        == 104.0
    # top-10% 8-bit: 10 B payload + 40 B indices + 4 B scale
    assert encoded_client_bytes(t, CodecConfig(topk_frac=0.1, bits=8)) \
        == 54.0
    # top-10% raw: 40 B payload + 40 B indices + 4 B scale
    assert encoded_client_bytes(t, CodecConfig(topk_frac=0.1, bits=0)) \
        == 84.0


# ---------------------------------------------------------------------------
# batched column-bounded quantizer (fused multi-leaf codec kernel)
# ---------------------------------------------------------------------------

def _cols_data(m, n, seed=0):
    key = jax.random.PRNGKey(seed)
    X = jax.random.normal(key, (m, n)) * 2.0
    F = jax.random.normal(jax.random.fold_in(key, 1), (m, n))
    kc = jax.random.randint(jax.random.fold_in(key, 2), (m,), 0, n + 1)
    live = jnp.arange(n)[None, :] < kc[:, None]
    s = jnp.max(jnp.where(live, jnp.abs(X), 0.0), axis=1)
    u32 = jax.random.bits(jax.random.fold_in(key, 3), (m, n),
                          dtype=jnp.uint32)
    return X, F, s, kc, u32


@pytest.mark.parametrize("m,n", [(1, 7), (5, 300), (32, 1024), (3, 513)])
@pytest.mark.parametrize("bits", [2, 8])
@pytest.mark.parametrize("stochastic", [True, False])
def test_quantize_cols_pallas_matches_ref_bitexact(m, n, bits, stochastic):
    """Same dither bits => the batched kernel and the jnp reference agree
    EXACTLY, per-row live-column bounds included."""
    X, F, s, kc, u32 = _cols_data(m, n, seed=m * n + 1)
    u = u32 if stochastic else None
    qp = ops.quantize_cols(X, F, s, kc, bits, u, impl="pallas",
                           interpret=True)
    qr = ops.quantize_cols(X, F, s, kc, bits, u, impl="ref")
    assert np.array_equal(np.asarray(qp), np.asarray(qr))
    assert qp.dtype == X.dtype


def test_quantize_cols_dead_columns_pass_fallback_bituntouched():
    """Columns at or past a row's live count return F exactly; live
    columns match the plain row-wise quantizer driven by the same scale."""
    X, F, s, kc, u32 = _cols_data(6, 128, seed=11)
    out = np.asarray(ops.quantize_cols(X, F, s, kc, 8, u32, impl="ref"))
    live = np.arange(128)[None, :] < np.asarray(kc)[:, None]
    np.testing.assert_array_equal(out[~live], np.asarray(F)[~live])
    full = np.asarray(ops.quantize(X, s, 8, u32, impl="ref"))
    np.testing.assert_array_equal(out[live], full[live])


def test_quantize_cols_zero_live_row_is_all_fallback():
    X, F, s, _, u32 = _cols_data(4, 64, seed=13)
    kc = jnp.zeros((4,), jnp.int32)
    for impl in ("ref", "pallas"):
        out = np.asarray(ops.quantize_cols(X, F, s, kc, 8, u32, impl=impl,
                                           interpret=True))
        np.testing.assert_array_equal(out, np.asarray(F))


@pytest.mark.parametrize("impl", ["ref", "pallas"])
def test_quantize_cols_shape_validation(impl):
    """Both impls must reject mismatched X/F (ref would otherwise silently
    broadcast the fallback)."""
    X, F, s, kc, _ = _cols_data(2, 16)
    with pytest.raises(ValueError):
        ops.quantize_cols(X, F[:1], s, kc, 8, None, impl=impl)
