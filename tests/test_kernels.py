"""Per-kernel validation: Pallas (interpret=True) vs pure-jnp oracle.

Sweeps shapes and dtypes per the deliverable spec; tolerances scale with
dtype (bf16 accumulates in f32 inside the kernels).
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import ops, ref
from repro.kernels.batched_gemm import batched_gemm_pallas
from repro.kernels.batched_qr import batched_qr_pallas
from repro.kernels.lr_sample import lr_sample_pallas
from repro.kernels.small_svd import small_svd_pallas
from repro.kernels.tlr_matvec import tile_chain_pallas

TOL = {
    jnp.float64: dict(rtol=1e-12, atol=1e-12),
    jnp.float32: dict(rtol=1e-5, atol=1e-5),
    jnp.bfloat16: dict(rtol=5e-2, atol=5e-2),
}


def _rand(key, shape, dtype):
    return jax.random.normal(key, shape, jnp.float32).astype(dtype)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.float64, jnp.bfloat16])
@pytest.mark.parametrize("T,k,b,r,s", [
    (1, 1, 32, 8, 8),
    (3, 4, 64, 16, 8),
    (2, 7, 128, 32, 16),
    (5, 2, 96, 24, 4),
])
def test_lr_sample_kernel(T, k, b, r, s, dtype):
    ks = jax.random.split(jax.random.PRNGKey(0), 3)
    Ui = _rand(ks[0], (T, k, b, r), dtype)
    Vi = _rand(ks[1], (T, k, b, r), dtype)
    W2 = _rand(ks[2], (k, b, s), dtype)
    got = lr_sample_pallas(Ui, Vi, W2, interpret=True)
    want = ref.lr_sample_ref(Ui, Vi, W2)
    assert got.dtype == dtype
    tol = TOL[dtype]
    np.testing.assert_allclose(
        np.asarray(got, np.float64), np.asarray(want, np.float64),
        rtol=tol["rtol"], atol=tol["atol"] * k * np.sqrt(b),
    )


def test_lr_sample_k_zero():
    Ui = jnp.zeros((2, 0, 32, 8))
    Vi = jnp.zeros((2, 0, 32, 8))
    W2 = jnp.zeros((0, 32, 4))
    out = lr_sample_pallas(Ui, Vi, W2, interpret=True)
    assert out.shape == (2, 32, 4)
    assert (np.asarray(out) == 0).all()


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.float64, jnp.bfloat16])
@pytest.mark.parametrize("T,m,k,n", [
    (1, 16, 8, 16),
    (4, 64, 32, 8),
    (3, 128, 64, 128),
])
def test_batched_gemm_kernel(T, m, k, n, dtype):
    ks = jax.random.split(jax.random.PRNGKey(1), 2)
    A = _rand(ks[0], (T, m, k), dtype)
    B = _rand(ks[1], (T, k, n), dtype)
    ranks = jnp.asarray(np.random.default_rng(0).integers(0, k + 1, T),
                        jnp.int32)
    got = batched_gemm_pallas(A, B, ranks, interpret=True)
    want = ref.batched_gemm_ref(A, B, ranks)
    tol = TOL[dtype]
    np.testing.assert_allclose(
        np.asarray(got, np.float64), np.asarray(want, np.float64),
        rtol=tol["rtol"], atol=tol["atol"] * np.sqrt(k),
    )


def test_batched_gemm_blocked_grid():
    """Output gridding (bm, bn) must not change results."""
    ks = jax.random.split(jax.random.PRNGKey(2), 2)
    A = _rand(ks[0], (2, 128, 32), jnp.float32)
    B = _rand(ks[1], (2, 32, 64), jnp.float32)
    ranks = jnp.asarray([32, 17], jnp.int32)
    got = batched_gemm_pallas(A, B, ranks, bm=64, bn=32, interpret=True)
    want = ref.batched_gemm_ref(A, B, ranks)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=1e-5,
                               atol=1e-5)


def test_batched_gemm_rank_masking():
    """rank=0 rows give exactly zero; full rank gives plain GEMM."""
    A = jnp.ones((2, 8, 4), jnp.float32)
    B = jnp.ones((2, 4, 8), jnp.float32)
    ranks = jnp.asarray([0, 4], jnp.int32)
    got = np.asarray(batched_gemm_pallas(A, B, ranks, interpret=True))
    assert (got[0] == 0).all()
    assert (got[1] == 4).all()


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.float64, jnp.bfloat16])
@pytest.mark.parametrize("T,b,r,s", [
    (1, 32, 8, 1),
    (6, 64, 16, 4),
    (3, 128, 48, 2),
])
def test_tile_chain_kernel(T, b, r, s, dtype):
    ks = jax.random.split(jax.random.PRNGKey(3), 3)
    U = _rand(ks[0], (T, b, r), dtype)
    V = _rand(ks[1], (T, b, r), dtype)
    X = _rand(ks[2], (T, b, s), dtype)
    got = tile_chain_pallas(U, V, X, interpret=True)
    want = ref.tile_chain_ref(U, V, X)
    tol = TOL[dtype]
    np.testing.assert_allclose(
        np.asarray(got, np.float64), np.asarray(want, np.float64),
        rtol=tol["rtol"], atol=tol["atol"] * np.sqrt(b),
    )


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.float64])
@pytest.mark.parametrize("T,b,r", [(1, 16, 4), (4, 32, 8), (3, 64, 16)])
def test_batched_qr_kernel(T, b, r, dtype):
    """MGS kernel vs the Householder oracle: both must satisfy the
    rounding-pass contract (Y ~= Q R, orthonormal live columns, R upper
    triangular) -- Q itself is not unique, so parity is on the contract."""
    Y = _rand(jax.random.PRNGKey(7), (T, b, r), dtype)
    for Q, R in (batched_qr_pallas(Y, interpret=True), ref.batched_qr_ref(Y)):
        tol = TOL[dtype]
        np.testing.assert_allclose(
            np.asarray(jnp.einsum("tbr,trs->tbs", Q, R), np.float64),
            np.asarray(Y, np.float64), rtol=tol["rtol"],
            atol=tol["atol"] * np.sqrt(b))
        gram = np.asarray(jnp.einsum("tbr,tbs->trs", Q, Q))
        np.testing.assert_allclose(gram, np.broadcast_to(np.eye(r), gram.shape),
                                   atol=10 * tol["atol"])
        assert np.allclose(np.asarray(R), np.triu(np.asarray(R)),
                           atol=tol["atol"])


def test_batched_qr_rank_deficient_drops_columns():
    """Dependent / zero columns must come out exactly zero in Q (inert in
    every downstream product), with the factorization still valid."""
    rng = np.random.default_rng(3)
    Y = rng.standard_normal((2, 24, 6))
    Y[0][:, 4] = 2.0 * Y[0][:, 1] - Y[0][:, 0]
    Y[1][:, 2] = 0.0
    Q, R = batched_qr_pallas(jnp.asarray(Y), interpret=True)
    Q = np.asarray(Q)
    assert np.abs(Q[0][:, 4]).max() == 0.0
    assert np.abs(Q[1][:, 2]).max() == 0.0
    np.testing.assert_allclose(np.einsum("tbr,trs->tbs", Q, np.asarray(R)),
                               Y, atol=1e-10)


@pytest.mark.parametrize("scale", [1e5, 1e-5])
def test_batched_qr_extreme_column_scales(scale):
    """Regression: the drop tolerance must follow the *current* column norms
    each sweep. With tol frozen at rel * max input norm, an f32 panel scaled
    by 1e5 makes tol >= 1 and sweep 2 (unit columns) zeroes everything."""
    Y = scale * _rand(jax.random.PRNGKey(11), (3, 32, 8), jnp.float32)
    Q, R = batched_qr_pallas(Y, interpret=True)
    np.testing.assert_allclose(
        np.asarray(jnp.einsum("tbr,trs->tbs", Q, R), np.float64),
        np.asarray(Y, np.float64), rtol=1e-4, atol=1e-4 * scale)
    gram = np.asarray(jnp.einsum("tbr,tbs->trs", Q, Q))
    np.testing.assert_allclose(gram, np.broadcast_to(np.eye(8), gram.shape),
                               atol=1e-3)


def test_batched_qr_rejects_wide_panels():
    with pytest.raises(ValueError, match="tall panels"):
        batched_qr_pallas(jnp.zeros((1, 8, 16)), interpret=True)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.float64])
@pytest.mark.parametrize("T,n", [(1, 4), (3, 8), (2, 16)])
def test_small_svd_kernel(T, n, dtype):
    """Jacobi kernel vs the LAPACK oracle: singular values and the
    reconstruction must agree (U/V columns carry a sign ambiguity)."""
    M = _rand(jax.random.PRNGKey(9), (T, n, n), dtype)
    got = ops.small_svd(M, impl="interpret")
    want = ref.small_svd_ref(M)
    tol = TOL[dtype]
    np.testing.assert_allclose(np.asarray(got[1], np.float64),
                               np.asarray(want[1], np.float64),
                               rtol=100 * tol["rtol"],
                               atol=100 * tol["atol"])
    for U, s, V in (got, want):
        rec = jnp.einsum("tmn,tn,tkn->tmk", U, s, V)
        np.testing.assert_allclose(np.asarray(rec, np.float64),
                                   np.asarray(M, np.float64),
                                   rtol=tol["rtol"],
                                   atol=100 * tol["atol"] * np.sqrt(n))


def test_small_svd_low_rank_and_sorting():
    rng = np.random.default_rng(5)
    M = np.einsum("tm,tn->tmn", rng.standard_normal((3, 10)),
                  rng.standard_normal((3, 10)))  # rank-1 batch
    U, s, V = ops.small_svd(jnp.asarray(M), impl="interpret")
    s = np.asarray(s)
    assert (np.diff(s, axis=-1) <= 1e-12).all()  # descending
    assert (s[:, 1:] < 1e-10 * s[:, :1]).all()   # rank 1
    with pytest.raises(ValueError, match="n <= m"):
        small_svd_pallas(jnp.zeros((1, 4, 8)), interpret=True)


def test_resolve_impl_rejects_pallas_off_tpu():
    """Satellite contract: impl='pallas' off-TPU must fail *up front* with
    an actionable message, not deep inside pallas_call."""
    if jax.default_backend() == "tpu":  # pragma: no cover
        pytest.skip("on TPU the pallas path is the real one")
    with pytest.raises(RuntimeError, match="requires a TPU backend"):
        ops.resolve_impl("pallas")
    with pytest.raises(RuntimeError, match="interpret"):
        ops.batched_gemm(jnp.zeros((1, 4, 4)), jnp.zeros((1, 4, 4)),
                         jnp.zeros((1,), jnp.int32), impl="pallas")
    with pytest.raises(ValueError, match="must be one of"):
        ops.resolve_impl("cuda")
    assert ops.resolve_impl(None) in ("ref", "pallas")
    assert ops.resolve_impl("interpret") == "interpret"


def test_batched_gemm_empty_batch():
    """A zero-tile batch (the pair grid of a one-row bucket in the
    right-looking trailing update) returns an empty result instead of
    tracing the kernel over an empty ranks array."""
    out = ops.batched_gemm(jnp.zeros((0, 8, 4)), jnp.zeros((0, 4, 3)),
                           jnp.zeros((0,), jnp.int32), impl="interpret")
    assert out.shape == (0, 8, 3)


@pytest.mark.parametrize("on_tpu", [False, True])
def test_default_impl_per_op(monkeypatch, on_tpu):
    """The backend default is resolved per op and never "interpret": on a
    TPU every op takes its kernel except those TPU_XLA_DEFAULT names (each
    with its reason); elsewhere every op takes the XLA path."""
    monkeypatch.setattr(ops, "_on_tpu", lambda: on_tpu)
    for op in ("lr_sample", "batched_gemm", "tile_chain", "batched_qr",
               "small_svd"):
        want = "pallas" if on_tpu and op not in ops.TPU_XLA_DEFAULT \
            else "ref"
        assert ops.resolve_impl(None, op) == want
    assert ops.resolve_impl(None) == ("pallas" if on_tpu else "ref")
    assert set(ops.TPU_XLA_DEFAULT) == {"small_svd"}
    assert all(ops.TPU_XLA_DEFAULT.values())
    if on_tpu:  # an explicit request still runs the kernel
        assert ops.resolve_impl("pallas", "small_svd") == "pallas"


def test_lr_sample_matches_factorization_sampling():
    """Kernel output == the einsum used inside the factorization samplers."""
    rng = np.random.default_rng(0)
    T, k, b, r, s = 3, 5, 64, 16, 8
    Ui = jnp.asarray(rng.standard_normal((T, k, b, r)))
    Vi = jnp.asarray(rng.standard_normal((T, k, b, r)))
    Uk = jnp.asarray(rng.standard_normal((k, b, r)))
    Vk = jnp.asarray(rng.standard_normal((k, b, r)))
    Om = jnp.asarray(rng.standard_normal((b, s)))
    # shared-omega hoisted intermediate
    W2 = jnp.einsum("jbr,jrs->jbs", Vk, jnp.einsum("jbr,bs->jrs", Uk, Om))
    got = lr_sample_pallas(Ui, Vi, W2, interpret=True)
    T3 = jnp.einsum("tjbr,jbs->tjrs", Vi, W2)
    want = jnp.einsum("tjbr,tjrs->tbs", Ui, T3)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=1e-10,
                               atol=1e-10)


def _f32(*shape, dtype=jnp.float32):
    return jax.ShapeDtypeStruct(shape, dtype)


KERNEL_LOWERINGS = {
    "lr_sample_pallas": (lambda u, v, w: lr_sample_pallas(
        u, v, w, interpret=False),
        (_f32(4, 2, 128, 16), _f32(4, 2, 128, 16), _f32(2, 128, 16))),
    "tile_chain_pallas": (lambda u, v, x: tile_chain_pallas(
        u, v, x, interpret=False),
        (_f32(4, 128, 16), _f32(4, 128, 16), _f32(4, 128, 8))),
    "batched_gemm_pallas": (lambda a, b, k: batched_gemm_pallas(
        a, b, k, interpret=False),
        (_f32(4, 128, 16), _f32(4, 16, 16), _f32(4, dtype=jnp.int32))),
    "batched_qr_pallas": (lambda y: batched_qr_pallas(y, interpret=False),
                          (_f32(4, 128, 16),)),
    "small_svd_pallas": (lambda m: small_svd_pallas(m, interpret=False),
                         (_f32(4, 16, 16),)),
}


@pytest.mark.parametrize("name", list(KERNEL_LOWERINGS))
def test_kernel_carries_its_name(name):
    """Each Mosaic custom call is named after its jitted wrapper, so the
    op in a device trace keeps that name whatever function holds the
    kernel (lowered for the TPU on the CPU; nothing is compiled)."""
    fn, shapes = KERNEL_LOWERINGS[name]
    with jax.enable_x64(False):
        text = jax.jit(fn).trace(*shapes).lower(
            lowering_platforms=("tpu",)).as_text()
    assert "tpu_custom_call" in text
    assert f'kernel_name = "{name}"' in text
