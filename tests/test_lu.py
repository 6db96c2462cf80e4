"""LU family tests — residuals per the reference's test/test_gesv.cc:
‖PA − LU‖ and backward error of solves.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import slate_tpu as st
from slate_tpu.core.types import MethodLU, Options
from slate_tpu.linalg import lu as lu_mod
from slate_tpu.matgen import generate_matrix

RNG = np.random.default_rng(23)


def _solve_residual(a, b, x):
    return (np.linalg.norm(b - a @ x, 1)
            / (np.linalg.norm(a, 1) * np.linalg.norm(x, 1)
               * a.shape[0] * np.finfo(float).eps))


@pytest.mark.parametrize("n,nb", [(48, 16), (50, 16), (33, 8)])
def test_getrf_residual(n, nb):
    a = RNG.standard_normal((n, n))
    A = st.from_dense(a, nb=nb)
    LU, perm, info = lu_mod.getrf(A)
    assert int(info) == 0
    lu = LU.to_numpy()
    l = np.tril(lu, -1) + np.eye(lu.shape[0])
    u = np.triu(lu)
    pa = np.pad(a, ((0, len(perm) - n), (0, len(perm) - n)))
    pa = lu_mod._pad_identity_diag(jnp.asarray(pa), n, n)
    pa = np.asarray(pa)[np.asarray(perm)][:n, :n]
    lfull = np.tril(np.asarray(LU.dense_canonical()), -1) + np.eye(len(perm))
    ufull = np.triu(np.asarray(LU.dense_canonical()))
    err = np.linalg.norm(pa - (lfull @ ufull)[:n, :n], 1) / (
        np.linalg.norm(a, 1) * n * np.finfo(float).eps)
    assert err < 10.0


@pytest.mark.parametrize("n,nb,nrhs", [(64, 16, 4), (37, 8, 3)])
def test_gesv(n, nb, nrhs):
    a = RNG.standard_normal((n, n))
    b = RNG.standard_normal((n, nrhs))
    X, info = st.gesv(st.from_dense(a, nb=nb), st.from_dense(b, nb=nb))
    assert int(info) == 0
    assert _solve_residual(a, b, X.to_numpy()) < 10.0


def test_gesv_trans():
    n, nrhs = 32, 2
    a = RNG.standard_normal((n, n))
    b = RNG.standard_normal((n, nrhs))
    LU, perm, info = lu_mod.getrf(st.from_dense(a, nb=8))
    X = lu_mod.getrs(LU, perm, st.from_dense(b, nb=8), trans=True)
    np.testing.assert_allclose(X.to_numpy(), np.linalg.solve(a.T, b),
                               rtol=1e-8, atol=1e-10)


@pytest.mark.slow  # ~5 s (round-10 headroom); mesh factor+solve stays
# covered by test_grid_matches_single_device + the getrs grid tests
def test_gesv_on_grid(grid2x2):
    n, nrhs = 64, 8
    a = RNG.standard_normal((n, n))
    b = RNG.standard_normal((n, nrhs))
    A = st.from_dense(a, nb=16, grid=grid2x2)
    B = st.from_dense(b, nb=16, grid=grid2x2)
    X, info = st.gesv(A, B)
    np.testing.assert_allclose(X.to_numpy(), np.linalg.solve(a, b),
                               rtol=1e-8, atol=1e-10)


def test_gesv_jit():
    n = 24
    a = RNG.standard_normal((n, n))
    b = RNG.standard_normal((n, 2))

    @jax.jit
    def f(A, B):
        return st.gesv(A, B)

    X, info = f(st.from_dense(a, nb=8), st.from_dense(b, nb=8))
    np.testing.assert_allclose(X.to_numpy(), np.linalg.solve(a, b),
                               rtol=1e-8, atol=1e-10)


def test_getrf_nopiv_dominant():
    n = 40
    a = np.asarray(generate_matrix("rand_dominant", n, n, jnp.float64, seed=4))
    b = RNG.standard_normal((n, 3))
    X, info = lu_mod.gesv_nopiv(st.from_dense(a, nb=16), st.from_dense(b, nb=16))
    assert int(info) == 0
    np.testing.assert_allclose(X.to_numpy(), np.linalg.solve(a, b),
                               rtol=1e-8, atol=1e-10)


def test_getrf_info_singular():
    n = 16
    a = RNG.standard_normal((n, n))
    a[:, 3] = 0.0  # exactly singular
    LU, perm, info = lu_mod.getrf(st.from_dense(a, nb=8))
    assert int(info) > 0


def test_getrf_tntpiv():
    n, nb = 64, 16
    a = RNG.standard_normal((n, n))
    b = RNG.standard_normal((n, 4))
    A = st.from_dense(a, nb=nb)
    LU, perm, info = lu_mod.getrf_tntpiv(A)
    assert int(info) == 0
    X = lu_mod.getrs(LU, perm, st.from_dense(b, nb=nb))
    assert _solve_residual(a, b, X.to_numpy()) < 50.0


@pytest.mark.slow  # ~9 s multi-method compile bill (round-10 headroom);
# each method keeps its own dedicated numerics test in tier-1
def test_gesv_method_dispatch():
    n = 32
    a = np.asarray(generate_matrix("rand_dominant", n, n, jnp.float64, seed=6))
    b = RNG.standard_normal((n, 2))
    for m in [MethodLU.PartialPiv, MethodLU.CALU, MethodLU.NoPiv, MethodLU.RBT]:
        X, info = st.gesv(st.from_dense(a, nb=8), st.from_dense(b, nb=8),
                          Options(method_lu=m))
        np.testing.assert_allclose(X.to_numpy(), np.linalg.solve(a, b),
                                   rtol=1e-6, atol=1e-8, err_msg=str(m))


def test_gesv_rbt():
    n = 64
    a = RNG.standard_normal((n, n))  # general, needs pivoting normally
    b = RNG.standard_normal((n, 2))
    X, info = lu_mod.gesv_rbt(st.from_dense(a, nb=16), st.from_dense(b, nb=16))
    res = _solve_residual(a, b, X.to_numpy())
    assert res < 1e4  # RBT trades stability for speed; IR recovers most


def test_getri():
    n = 30
    a = RNG.standard_normal((n, n)) + 5 * np.eye(n)
    LU, perm, info = lu_mod.getrf(st.from_dense(a, nb=8))
    Ainv = lu_mod.getri(LU, perm)
    np.testing.assert_allclose(Ainv.to_numpy(), np.linalg.inv(a),
                               rtol=1e-7, atol=1e-9)


def test_gesv_mixed():
    n = 48
    a = RNG.standard_normal((n, n)) + 8 * np.eye(n)
    b = RNG.standard_normal((n, 2))
    A = st.from_dense(a, nb=16)
    B = st.from_dense(b, nb=16)
    X, info, iters = lu_mod.gesv_mixed(A, B, factor_dtype=jnp.float32)
    assert int(info) == 0 and iters != 0
    res = np.linalg.norm(b - a @ X.to_numpy(), np.inf) / (
        np.linalg.norm(a, np.inf) * np.linalg.norm(X.to_numpy(), np.inf))
    assert res < 1e-13


@pytest.mark.slow  # ~6 s n=192/nb=64 compile (round-22 tier-1
# budget); tier-1 sibling test_getrf_pivot_threshold_recursive_base
# keeps the CALU tournament path pinned on a tall single panel
def test_getrf_pivot_threshold_tournament():
    """pivot_threshold < 1 (the Option::PivotThreshold analog) swaps the
    panel's argmax/swap chain for the vmap-batched CALU tournament."""
    from slate_tpu.core.types import Options
    n = 192
    rng = np.random.default_rng(9)
    a = rng.standard_normal((n, n))
    A = st.from_dense(a, nb=64)
    LU, perm, info = st.getrf(A, Options(pivot_threshold=0.5))
    lu = np.asarray(LU.dense_canonical(), np.float64)
    npad = lu.shape[0]
    l = np.tril(lu, -1) + np.eye(npad)
    u = np.triu(lu)
    pa = np.asarray(A.dense_canonical(), np.float64)[np.asarray(perm)]
    assert np.abs(pa - l @ u).max() < n * 1e-13
    b = rng.standard_normal((n, 3))
    X = st.getrs(LU, perm, st.from_dense(b, nb=64))
    assert np.abs(a @ X.to_numpy() - b).max() < n * 1e-12


def test_getrf_pivot_threshold_recursive_base(monkeypatch):
    """Tall single-panel shape through _getrf_rec's tournament base
    (reached with the iterative loop's nt guard below the shape's)."""
    from slate_tpu.core.types import Options
    monkeypatch.setattr(lu_mod, "_ITER_MAX_NT", 0)
    m, n, nb = 160, 32, 32
    rng = np.random.default_rng(11)
    a = rng.standard_normal((m, n))
    A = st.from_dense(a, nb=nb)
    LU, perm, info = st.getrf(A, Options(pivot_threshold=0.5))
    lu = np.asarray(LU.dense_canonical(), np.float64)
    mpad = lu.shape[0]
    l = np.tril(lu, -1)[:, :n] + np.eye(mpad, n)
    u = np.triu(lu)[:n, :]
    pa = np.asarray(A.dense_canonical(), np.float64)[np.asarray(perm)]
    assert np.abs(pa - l @ u).max() < m * 1e-13


def test_getrf_rec_iter_base_dispatch(monkeypatch):
    """The hybrid dispatch past the HLO-size guard: the width recursion
    above it, the flat iterative loop as its base case. With the guard
    lowered to nt = 4, n=128 at nb=16 (nt = 8) must split once in
    _getrf_rec and factor each 64-wide half with _getrf_iter. Verifies
    the residual AND the solve built on it."""
    monkeypatch.setattr(lu_mod, "_ITER_MAX_NT", 4)
    calls = {"iter": 0, "rec": 0}
    for name in ("_getrf_iter", "_getrf_rec"):
        orig = getattr(lu_mod, name)
        key = name.split("_")[-1]

        def spy(*a, _o=orig, _k=key, **kw):
            calls[_k] += 1
            return _o(*a, **kw)

        monkeypatch.setattr(lu_mod, name, spy)

    n, nb = 128, 16  # nt 8 > 4 -> rec splits; halves nt 4 -> iter
    a = RNG.standard_normal((n, n))
    A = st.from_dense(a, nb=nb)
    LU, perm, info = lu_mod.getrf(A)
    assert int(info) == 0
    assert calls["rec"] >= 1 and calls["iter"] == 2
    lu = np.asarray(LU.dense_canonical())
    l = np.tril(lu, -1) + np.eye(len(perm))
    u = np.triu(lu)
    pa = np.asarray(lu_mod._pad_identity_diag(
        jnp.asarray(np.pad(a, ((0, len(perm) - n), (0, len(perm) - n)))),
        n, n))[np.asarray(perm)]
    err = np.linalg.norm(pa[:n, :n] - (l @ u)[:n, :n], 1) / (
        np.linalg.norm(a, 1) * n * np.finfo(float).eps)
    assert err < 10.0
    b = RNG.standard_normal((n, 3))
    X = lu_mod.getrs(LU, jnp.asarray(perm), st.from_dense(b, nb=nb))
    assert _solve_residual(a, b, X.to_numpy()) < 30.0


@pytest.mark.slow  # ~10 s (round-10 headroom); threshold/tournament
# pivoting stays pinned by test_getrf_pivot_threshold_tournament
def test_getrf_rec_tournament_threshold(monkeypatch):
    """pivot_threshold < 1 past the lowered nt guard: the recursion's
    full-gather permutation composition (threshold < 1 path) composes
    with _getrf_iter's tournament (compaction-perm) panels — pin that
    composition stays correct."""
    monkeypatch.setattr(lu_mod, "_ITER_MAX_NT", 4)
    n, nb = 128, 16
    a = RNG.standard_normal((n, n))
    A = st.from_dense(a, nb=nb)
    LU, perm, info = lu_mod.getrf(A, Options(pivot_threshold=0.5))
    assert int(info) == 0
    lu = np.asarray(LU.dense_canonical())
    l = np.tril(lu, -1) + np.eye(len(perm))
    u = np.triu(lu)
    pa = np.asarray(lu_mod._pad_identity_diag(
        jnp.asarray(np.pad(a, ((0, len(perm) - n), (0, len(perm) - n)))),
        n, n))[np.asarray(perm)]
    # tournament pivot growth is weaker than partial pivoting's; keep a
    # looser bound (same spirit as test_getrf_pivot_threshold_tournament)
    err = np.linalg.norm(pa[:n, :n] - (l @ u)[:n, :n], 1) / (
        np.linalg.norm(a, 1) * n * np.finfo(float).eps)
    assert err < 100.0
