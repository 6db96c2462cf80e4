"""Cholesky family tests with the reference's residual self-checks.

Reference: test/test_posv.cc — residual ‖B − A·X‖ / (‖A‖·‖X‖·n·ε) and
factor residual ‖A − L·Lᴴ‖ / (‖A‖·n·ε).
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import slate_tpu as st
from slate_tpu.core.types import Norm, Uplo
from slate_tpu.matgen import random_spd

RNG = np.random.default_rng(11)


def _residual_factor(a, L):
    l = np.tril(L.to_numpy())
    return (np.linalg.norm(a - l @ l.conj().T, 1)
            / (np.linalg.norm(a, 1) * a.shape[0] * np.finfo(a.real.dtype).eps))


@pytest.mark.parametrize("n,nb", [(50, 16), (64, 16), (33, 8)])
def test_potrf_lower(n, nb):
    a = np.asarray(random_spd(n, dtype=jnp.float64, seed=n))
    A = st.hermitian(np.tril(a), nb=nb, uplo=Uplo.Lower)
    L, info = st.potrf(A)
    assert int(info) == 0
    assert _residual_factor(a, L) < 3.0


def test_potrf_upper():
    n = 40
    a = np.asarray(random_spd(n, dtype=jnp.float64, seed=2))
    A = st.hermitian(np.triu(a), nb=16, uplo=Uplo.Upper)
    U, info = st.potrf(A)
    assert int(info) == 0
    u = np.triu(U.to_numpy())
    err = np.linalg.norm(a - u.conj().T @ u, 1) / (
        np.linalg.norm(a, 1) * n * np.finfo(float).eps)
    assert err < 3.0


def test_potrf_complex():
    n = 24
    g = RNG.standard_normal((n, n)) + 1j * RNG.standard_normal((n, n))
    a = g @ g.conj().T / n + np.eye(n)
    A = st.hermitian(np.tril(a), nb=8, uplo=Uplo.Lower)
    L, info = st.potrf(A)
    assert int(info) == 0
    assert _residual_factor(a, L) < 3.0


def test_potrf_not_spd_info():
    n = 16
    a = np.eye(n)
    a[5, 5] = -1.0  # indefinite
    A = st.hermitian(a, nb=8, uplo=Uplo.Lower)
    L, info = st.potrf(A)
    assert int(info) == 6  # 1-based index of failing minor


def test_posv_residual():
    n, nrhs = 60, 4
    a = np.asarray(random_spd(n, dtype=jnp.float64, seed=5))
    b = RNG.standard_normal((n, nrhs))
    A = st.hermitian(np.tril(a), nb=16, uplo=Uplo.Lower)
    B = st.from_dense(b, nb=16)
    X, info = st.posv(A, B)
    assert int(info) == 0
    x = X.to_numpy()
    res = np.linalg.norm(b - a @ x, 1) / (
        np.linalg.norm(a, 1) * np.linalg.norm(x, 1) * n * np.finfo(float).eps)
    assert res < 3.0


@pytest.mark.slow  # ~7 s mesh posv compile (round-22 tier-1 budget);
# tier-1 siblings — test_posv_residual (posv numerics) and
# test_uneven_grid.py::test_posv_uneven_grid (posv on a mesh)
def test_posv_on_grid(grid2x2):
    n, nrhs = 64, 8
    a = np.asarray(random_spd(n, dtype=jnp.float64, seed=9))
    b = RNG.standard_normal((n, nrhs))
    A = st.hermitian(np.tril(a), nb=16, uplo=Uplo.Lower, grid=grid2x2)
    B = st.from_dense(b, nb=16, grid=grid2x2)
    X, info = st.posv(A, B)
    np.testing.assert_allclose(X.to_numpy(), np.linalg.solve(a, b), rtol=1e-8)


def test_posv_jit():
    n, nrhs = 32, 3
    a = np.asarray(random_spd(n, dtype=jnp.float64, seed=13))
    b = RNG.standard_normal((n, nrhs))
    A = st.hermitian(np.tril(a), nb=16, uplo=Uplo.Lower)
    B = st.from_dense(b, nb=16)

    @jax.jit
    def solve(A, B):
        return st.posv(A, B)

    X, info = solve(A, B)
    np.testing.assert_allclose(X.to_numpy(), np.linalg.solve(a, b), rtol=1e-8)


def test_trtri_potri():
    n = 28
    t = np.tril(RNG.standard_normal((n, n))) + 4 * np.eye(n)
    T = st.triangular(t, nb=8, uplo=Uplo.Lower)
    Tinv = st.trtri(T)
    np.testing.assert_allclose(np.tril(Tinv.to_numpy()), np.linalg.inv(t),
                               rtol=1e-9, atol=1e-10)
    # potri: A^-1 from factor
    a = np.asarray(random_spd(n, dtype=jnp.float64, seed=3))
    A = st.hermitian(np.tril(a), nb=8, uplo=Uplo.Lower)
    L, _ = st.potrf(A)
    Ainv = st.potri(L)
    np.testing.assert_allclose(Ainv.full_dense()[:n, :n], np.linalg.inv(a),
                               rtol=1e-7, atol=1e-9)


def test_posv_mixed():
    n, nrhs = 48, 2
    a = np.asarray(random_spd(n, dtype=jnp.float64, seed=21))
    b = RNG.standard_normal((n, nrhs))
    A = st.hermitian(np.tril(a), nb=16, uplo=Uplo.Lower)
    B = st.from_dense(b, nb=16)
    X, info, iters = st.posv_mixed(A, B, factor_dtype=jnp.float32)
    assert int(info) == 0
    assert iters != 0  # at least one refinement step happened
    x = X.to_numpy()
    # converged to double-precision accuracy despite f32 factorization
    res = np.linalg.norm(b - a @ x, np.inf) / (
        np.linalg.norm(a, np.inf) * np.linalg.norm(x, np.inf))
    assert res < 1e-13


@pytest.mark.slow  # ~8 s dispatch-policy probe (round-10 headroom);
# potrf numerics and the fastpaths dispatch probes stay tier-1
def test_potrf_rec_iter_base_dispatch(monkeypatch):
    """The hybrid dispatch past the HLO-size guard: 2x2 recursion
    above it, iterative loop as its base case. With the guard lowered
    to nt = 4, n=128 at nb=16 (nt = 8) must split once in _potrf_rec
    and factor each 64-half with _potrf_iter."""
    from slate_tpu.linalg import cholesky as chol_mod

    monkeypatch.setattr(chol_mod, "_ITER_MAX_NT", 4)
    calls = {"iter": 0, "rec": 0}
    for name in ("_potrf_iter", "_potrf_rec"):
        orig = getattr(chol_mod, name)
        key = name.split("_")[-1]

        def spy(*a, _o=orig, _k=key, **kw):
            calls[_k] += 1
            return _o(*a, **kw)

        monkeypatch.setattr(chol_mod, name, spy)

    n, nb = 128, 16  # nt 8 > 4 -> rec splits; 64-halves -> iter
    a = np.asarray(random_spd(n, dtype=jnp.float64, seed=77))
    A = st.hermitian(np.tril(a), nb=nb, uplo=Uplo.Lower)
    L, info = st.potrf(A)
    assert int(info) == 0
    assert calls["rec"] >= 1 and calls["iter"] == 2
    assert _residual_factor(a, L) < 3.0


@pytest.mark.slow  # ~6 s: two n=128 dispatch-variant compiles
# (round-22 tier-1 budget); tier-1 siblings — test_potrf_not_spd_info
# (the info contract) and test_potrf_rec_iter_base_dispatch (the
# hybrid rec->iter dispatch wiring)
def test_potrf_hybrid_info_offset(monkeypatch):
    """Non-SPD pivot inside the SECOND recursion half reports the
    correct absolute 1-based LAPACK info index through the hybrid
    rec->iter dispatch — and identically through the iterative
    in-place loop alone."""
    from slate_tpu.linalg import cholesky as chol_mod

    n, nb = 128, 16  # halves cover columns [0,64) [64,128)
    a = np.array(random_spd(n, dtype=jnp.float64, seed=79))
    bad = 100  # 0-based, inside the second half
    a[bad, bad] = -(abs(a).sum())  # dominate: leading minor fails there
    A = st.hermitian(np.tril(a), nb=nb, uplo=Uplo.Lower)
    L, info = st.potrf(A)  # nt 8: the iterative in-place loop
    assert int(info) == bad + 1
    monkeypatch.setattr(chol_mod, "_ITER_MAX_NT", 4)  # rec -> iter
    L, info = st.potrf(A)
    assert int(info) == bad + 1


def test_potrf_complex_ignores_imag_diagonal():
    """zpotrf contract: imaginary parts of the diagonal are assumed
    zero and ignored. The de-mirrored driver (round 5) must realify
    explicitly — full_dense used to do it implicitly."""
    n, nb = 96, 32
    x = RNG.standard_normal((n, n)) + 1j * RNG.standard_normal((n, n))
    a = (x @ x.conj().T + n * np.eye(n)).astype(np.complex128)
    stray = np.tril(a).copy()
    stray[np.arange(n), np.arange(n)] += 1j * RNG.standard_normal(n)
    A = st.hermitian(stray, nb=nb, uplo=Uplo.Lower)
    L, info = st.potrf(A)
    assert int(info) == 0
    l = L.to_numpy()
    r = np.linalg.norm(a - l @ l.conj().T) / (
        n * np.finfo(np.float64).eps * np.linalg.norm(a))
    assert r < 10
