"""Core data-model tests.

Mirrors the reference's unit_test/test_Matrix.cc (constructors, views,
sub, slice, transpose) and test_func.cc (distribution index maps).
"""

import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import slate_tpu as st
from slate_tpu.core.grid import (cyclic_permutation, inverse_permutation,
                                 num_tiles, tile_dim, tile_rank_2d)
from slate_tpu.core.types import Diag, MatrixKind, Op, Uplo


def test_num_tiles_and_dim():
    assert num_tiles(100, 32) == 4
    assert num_tiles(96, 32) == 3
    assert tile_dim(3, 100, 32) == 4
    assert tile_dim(0, 100, 32) == 32
    assert tile_dim(2, 96, 32) == 32


def test_tile_rank_2d():
    # 2D block-cyclic: tile (i, j) -> (i mod p, j mod q) (func.hh:100)
    p, q = 2, 3
    ranks = {(i, j): tile_rank_2d(i, j, p, q) for i in range(4) for j in range(6)}
    assert ranks[(0, 0)] == ranks[(2, 0)] == ranks[(0, 3)]
    assert len(set(ranks.values())) == p * q


def test_cyclic_permutation_roundtrip():
    for nt, p in [(7, 2), (8, 4), (5, 3), (1, 4)]:
        perm = cyclic_permutation(nt, p)
        inv = inverse_permutation(perm)
        for i in range(nt):
            assert perm[inv[i]] == i
        per = -(-nt // p)
        for pi in range(p):
            chunk = perm[pi * per:(pi + 1) * per]
            owned = [t for t in chunk if t >= 0]
            assert all(t % p == pi for t in owned)


def test_from_dense_roundtrip():
    a = np.arange(30.0).reshape(5, 6)
    A = st.from_dense(a, nb=4)
    assert A.data.shape == (8, 8)  # padded
    assert A.shape == (5, 6)
    assert A.mt == 2 and A.nt == 2
    np.testing.assert_array_equal(A.to_numpy(), a)


def test_transpose_views():
    a = np.arange(12.0).reshape(3, 4)
    A = st.from_dense(a, nb=2)
    At = A.T
    assert At.shape == (4, 3)
    np.testing.assert_array_equal(At.to_numpy(), a.T)
    np.testing.assert_array_equal(At.T.to_numpy(), a)
    # conj transpose on complex
    c = (a + 1j * a).astype(np.complex64)
    C = st.from_dense(c, nb=2)
    np.testing.assert_array_equal(C.H.to_numpy(), c.conj().T)
    np.testing.assert_array_equal(C.H.H.to_numpy(), c)
    np.testing.assert_array_equal(C.T.H.to_numpy(), c.conj())


def test_tile_access():
    a = np.arange(64.0).reshape(8, 8)
    A = st.from_dense(a, nb=4)
    np.testing.assert_array_equal(np.asarray(A.tile(1, 0)), a[4:8, 0:4])
    B = A.with_tile(0, 1, jnp.zeros((4, 4)))
    out = B.to_numpy()
    assert (out[0:4, 4:8] == 0).all()
    assert (out[4:8, 0:4] == a[4:8, 0:4]).all()


def test_sub_and_slice():
    a = np.arange(81.0).reshape(9, 9)
    A = st.from_dense(a, nb=3)
    S = A.sub(1, 2, 0, 1)
    np.testing.assert_array_equal(S.to_numpy(), a[3:9, 0:6])
    Z = A.slice(2, 6, 1, 7)
    np.testing.assert_array_equal(Z.to_numpy(), a[2:7, 1:8])


def test_full_dense_symmetric_hermitian():
    a = np.triu(np.arange(16.0).reshape(4, 4)) + 4 * np.eye(4)
    A = st.symmetric(a, nb=2, uplo=Uplo.Upper)
    f = np.asarray(A.full_dense())
    np.testing.assert_array_equal(f, np.triu(a) + np.triu(a, 1).T)

    c = (np.tril(np.arange(16.0).reshape(4, 4)) + 1j * np.tril(np.ones((4, 4)), -1))
    c = c.astype(np.complex128)
    H = st.hermitian(c, nb=2, uplo=Uplo.Lower)
    f = np.asarray(H.full_dense())
    np.testing.assert_allclose(f, np.tril(c) + np.tril(c, -1).conj().T)
    assert np.allclose(np.imag(np.diagonal(f)), 0)


def test_full_dense_triangular_unit():
    a = np.arange(16.0).reshape(4, 4) + 1
    T = st.triangular(a, nb=2, uplo=Uplo.Lower, diag=Diag.Unit)
    f = np.asarray(T.full_dense())
    expect = np.tril(a, -1) + np.eye(4)
    np.testing.assert_array_equal(f, expect)


def test_band_mask():
    a = np.ones((6, 6))
    B = st.band(a, nb=2, kl=1, ku=2)
    f = np.asarray(B.full_dense())[:6, :6]
    r, c = np.indices((6, 6))
    expect = ((c - r <= 2) & (r - c <= 1)).astype(float)
    np.testing.assert_array_equal(f, expect)


def test_shard_2x2(grid2x2):
    a = np.arange(64.0).reshape(8, 8)
    A = st.from_dense(a, nb=2, grid=grid2x2)
    assert len(A.data.sharding.device_set) == 4
    np.testing.assert_array_equal(A.to_numpy(), a)


def test_pytree_jit_roundtrip():
    a = np.arange(16.0).reshape(4, 4)
    A = st.from_dense(a, nb=2)

    @jax.jit
    def f(M: st.TiledMatrix):
        return M.with_data(M.data * 2.0)

    B = f(A)
    np.testing.assert_array_equal(B.to_numpy(), 2 * a)
    assert B.nb == 2 and B.shape == (4, 4)


def test_pad_diag_identity():
    a = np.eye(5) * 3.0
    A = st.from_dense(a, nb=4)  # padded to 8x8
    P = st.pad_diag_identity(A)
    d = np.asarray(P.data)
    assert (np.diagonal(d)[5:] == 1.0).all()
    np.testing.assert_array_equal(P.to_numpy(), a)


@pytest.mark.slow  # ~14 s (round-10 headroom); trtri stays covered by
# the compat trtri test and every trsm-consuming factorization suite
def test_trtri_lower_batched_matches_recursion():
    """The batched-leaf inverse (round-4 panel kernel) against the plain
    recursion and numpy, unit and non-unit, aligned and fallback.
    Inputs carry garbage in the strict upper triangle (must be ignored)
    and a non-unit stored diagonal in the unit case (unit=True must
    ignore the stored diagonal)."""
    from slate_tpu.ops import blocked

    rng = np.random.default_rng(0)
    for n, leaf in ((256, 64), (1024, 64), (96, 64)):  # 96: fallback
        # scale off-diagonals down: a random triangle's inverse grows
        # exponentially in n, which would swamp any entrywise check
        l = np.tril(rng.standard_normal((n, n))) / np.sqrt(n)
        l[np.arange(n), np.arange(n)] = 2.0 + np.abs(l.diagonal())
        # garbage above the diagonal: only the lower triangle is read
        lu = l + np.triu(rng.standard_normal((n, n)), 1) * 1e3
        for unit in (False, True):
            got = np.asarray(blocked.trtri_lower_batched(
                jnp.asarray(lu, jnp.float64), unit=unit, leaf=leaf))
            # the effective matrix: stored diagonal for non-unit,
            # implicit ones (stored diagonal IGNORED) for unit
            tl = np.tril(lu)
            if unit:
                tl = np.tril(lu, -1) + np.eye(n)
            res = np.abs(tl @ got - np.eye(n)).max()
            bound = n * 1e-14 * np.linalg.norm(tl, 1) * np.linalg.norm(
                got, 1)
            assert res < bound, (n, leaf, unit, res, bound)
            rec = np.asarray(blocked.trtri_lower_rec(
                jnp.asarray(lu, jnp.float64), unit=unit))
            rel = np.abs(got - rec).max() / max(np.abs(rec).max(), 1.0)
            assert rel < n * 1e-14


def test_trtri_lower_batched_complex():
    from slate_tpu.ops import blocked

    rng = np.random.default_rng(1)
    n = 128
    l = np.tril(rng.standard_normal((n, n))
                + 1j * rng.standard_normal((n, n))) / np.sqrt(n)
    l[np.arange(n), np.arange(n)] = 2.0 + np.abs(l.diagonal())
    got = np.asarray(blocked.trtri_lower_batched(
        jnp.asarray(l, jnp.complex128)))
    res = np.abs(l @ got - np.eye(n)).max()
    assert res < n * 1e-14 * np.linalg.norm(l, 1) * np.linalg.norm(got, 1)


TRSM_BASE_T = 128
TRSM_CASES = [(n, lower, unit, trans_a, left, "float64")
              for n in (384, 320)  # whole base blocks; ragged
              for lower in (True, False) for unit in (False, True)
              for trans_a in (False, True) for left in (True, False)]
TRSM_CASES.append((384, True, False, True, False, "complex64"))


@pytest.mark.parametrize("n, lower, unit, trans_a, left, dtype",
                         TRSM_CASES)
def test_trsm_rec_matches_solve_triangular(n, lower, unit, trans_a, left,
                                           dtype):
    """trsm_rec against jax.scipy's solve_triangular, on both of its
    paths: the sweep's diagonal blocks inverted up front in one batch
    (n a multiple of base) and each leaf inverting its own (ragged n).
    The other triangle holds garbage and, for unit, the stored diagonal
    is not one: neither may be read."""
    from jax.scipy.linalg import solve_triangular
    from slate_tpu.ops import blocked

    rng = np.random.default_rng(n + 2 * lower + 4 * unit + 8 * trans_a
                                + 16 * left)
    a = rng.standard_normal((n, n)) / np.sqrt(n)
    if dtype == "complex64":
        a = a + 1j * rng.standard_normal((n, n)) / np.sqrt(n)
    a[np.arange(n), np.arange(n)] = 2.0 + np.abs(a.diagonal())
    b = rng.standard_normal((n, 16) if left else (16, n)).astype(dtype)
    a, b = jnp.asarray(a, dtype), jnp.asarray(b)
    got = blocked.trsm_rec(a, b, left=left, lower=lower, unit=unit,
                           trans_a=trans_a, base=TRSM_BASE_T)
    # X·op(A) = B  ⇔  op(A)ᵀ·Xᵀ = Bᵀ
    trans = int(trans_a) if left else int(not trans_a)
    want = solve_triangular(a, b if left else b.T, trans=trans,
                            lower=lower, unit_diagonal=unit)
    want = np.asarray(want if left else want.T)
    tol = 1e-4 if dtype == "complex64" else 1e-12
    assert np.abs(np.asarray(got) - want).max() <= tol * np.abs(want).max()


def _trsm_hlo(n, lower):
    from slate_tpu.ops import blocked

    def sweep(a, b):
        return blocked.trsm_rec(a, b, lower=lower, base=TRSM_BASE_T)

    return jax.jit(sweep).lower(
        jax.ShapeDtypeStruct((n, n), jnp.float64),
        jax.ShapeDtypeStruct((n, 16), jnp.float64)).as_text(
            dialect="hlo", debug_info=True)


@pytest.mark.parametrize("lower", [True, False])
def test_trsm_rec_inverts_its_diagonal_blocks_in_one_batch(lower):
    """At n = 4·base a sweep lowers no loop: its four diagonal blocks are
    inverted by ONE leaf kernel under ``trsm_diag_inv`` — 64 row
    substitutions (one divide each), each over all 4·(base/64) leaves."""
    from slate_tpu.ops import blocked

    n = 4 * TRSM_BASE_T
    text = _trsm_hlo(n, lower)
    assert not re.search(r"\bwhile\(", text)
    divides = [ln for ln in text.splitlines() if " divide(" in ln]
    assert divides and all("/trsm_diag_inv/" in ln for ln in divides)
    assert len(divides) == blocked.TRTRI_BASE
    leaves = 4 * TRSM_BASE_T // blocked.TRTRI_BASE
    assert all(f"f64[{leaves},{blocked.TRTRI_BASE}]" in ln for ln in divides)


def test_trsm_rec_ragged_sweep_inverts_leaf_by_leaf():
    """A ragged n (3.5 base blocks) keeps each leaf's own inverse: the
    fori_loop substitutions, and no ``trsm_diag_inv``."""
    text = _trsm_hlo(7 * TRSM_BASE_T // 2, True)
    assert re.search(r"\bwhile\(", text)
    assert "trsm_diag_inv" not in text
