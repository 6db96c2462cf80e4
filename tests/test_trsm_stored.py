"""``st.trsm`` reads a Triangular operand as stored.

A Triangular operand's other triangle, and its diagonal when the
operand is ``Diag.Unit``, are never read: both solvers (the gemm
recursion and ``MethodTrsm.B``'s substitution) get the stored array,
with a transposed view solved through ``trans_a``/``conj_a``. NaN put
there must leave the answer bit-identical to that of a clean operand.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from scipy.linalg import solve_triangular

import slate_tpu as st
from slate_tpu.core.tiled_matrix import unit_pad_diag
from slate_tpu.core.types import Diag, MethodTrsm, Op, Options, Side, Uplo
from slate_tpu.ops import blocked

# (n, nb): whole blocks with power-of-two leaves (the sweep's batched
# diagonal inverses); a ragged n (a padded diagonal to unit-pad) in
# blocks that are no power-of-two multiple of 64 (each leaf inverts its
# own block)
SHAPES = [(256, 128), (200, 96)]
NRHS = 6
OPS = {"N": Op.NoTrans, "T": Op.Trans, "C": Op.ConjTrans}
PREC = Options().update_precision


def _view(T, op):
    return {Op.NoTrans: T, Op.Trans: T.T, Op.ConjTrans: T.H}[op]


def _operands(n, stored, diag, op, seed):
    """(clean, poisoned) stored triangles: the poisoned one holds NaN in
    the triangle the solve must not read, and on a unit diagonal."""
    rng = np.random.default_rng(seed)
    dtype = np.complex128 if op is Op.ConjTrans else np.float64
    t = rng.standard_normal((n, n)) / np.sqrt(n)
    if op is Op.ConjTrans:
        t = t + 1j * rng.standard_normal((n, n)) / np.sqrt(n)
    t[np.arange(n), np.arange(n)] = 2.0 + np.abs(t.diagonal())
    keep = np.tril if stored is Uplo.Lower else np.triu
    clean = keep(t).astype(dtype)
    bad = clean.copy()
    other = np.triu(np.ones((n, n), bool), 1)
    bad[other if stored is Uplo.Lower else other.T] = np.nan
    if diag is Diag.Unit:
        bad[np.arange(n), np.arange(n)] = np.nan
    return clean, bad


def _reference(tri, b, side, diag, op):
    """X with op(T)·X = b (Left) or X·op(T) = b (Right), by scipy."""
    if diag is Diag.Unit:
        tri = tri - np.diag(tri.diagonal()) + np.eye(tri.shape[0])
    lower = bool(np.allclose(tri, np.tril(tri)))
    trans = {Op.NoTrans: 0, Op.Trans: 1, Op.ConjTrans: 2}[op]
    if side is Side.Left:
        return solve_triangular(tri, b, trans=trans, lower=lower)
    # X·op(T) = b  ⇔  op(T)ᵀ·Xᵀ = bᵀ
    if op is Op.NoTrans:
        return solve_triangular(tri, b.T, trans=1, lower=lower).T
    if op is Op.Trans:
        return solve_triangular(tri, b.T, trans=0, lower=lower).T
    return solve_triangular(tri.conj(), b.T, trans=0, lower=lower).T


@pytest.mark.parametrize("n, nb", SHAPES)
@pytest.mark.parametrize("diag", [Diag.NonUnit, Diag.Unit])
@pytest.mark.parametrize("op", sorted(OPS))
@pytest.mark.parametrize("stored", [Uplo.Lower, Uplo.Upper])
@pytest.mark.parametrize("side", [Side.Left, Side.Right])
def test_trsm_reads_only_the_referenced_triangle(side, stored, op, diag, n,
                                                 nb):
    op = OPS[op]
    seed = (n + 7 * (side is Side.Left) + 11 * (stored is Uplo.Lower)
            + 13 * list(OPS.values()).index(op) + 17 * (diag is Diag.Unit))
    clean, bad = _operands(n, stored, diag, op, seed)
    rng = np.random.default_rng(seed + 1)
    b = rng.standard_normal((n, NRHS) if side is Side.Left else (NRHS, n))
    B = st.from_dense(b.astype(clean.dtype), nb=nb)
    want = _reference(clean, b, side, diag, op)

    for method in (MethodTrsm.Auto, MethodTrsm.B):
        opts = Options(method_trsm=method)

        @jax.jit
        def solve(t, B):
            T = st.triangular(t, nb=nb, uplo=stored, diag=diag)
            return st.trsm(side, 1.0, _view(T, op), B, opts).data

        got_clean = np.asarray(solve(clean, B))
        got_bad = np.asarray(solve(bad, B))
        assert np.isfinite(got_bad).all(), method
        np.testing.assert_array_equal(got_bad, got_clean, err_msg=str(method))
        mm, nn = want.shape
        err = np.abs(got_clean[:mm, :nn] - want).max() / np.abs(want).max()
        assert err < 1e-11, (method, err)


@pytest.mark.parametrize("n, nb", SHAPES)
@pytest.mark.parametrize("uplo", [Uplo.Lower, Uplo.Upper])
def test_potrs_matches_the_masked_copy_recipe(uplo, n, nb):
    """potrs's X (posv's solve) is bit-identical to the answer of the
    solve that materialized the masked triangles L and Lᴴ, unit-padded,
    before its two sweeps: reading the factor as stored changes no
    value."""
    rng = np.random.default_rng(2024)
    g = rng.standard_normal((n, n))
    a = g @ g.T / n + np.eye(n)
    l = np.linalg.cholesky(a)
    b = rng.standard_normal((n, NRHS))
    lower = uplo is Uplo.Lower
    F = st.triangular(l if lower else l.T, nb=nb, uplo=uplo)
    B = st.from_dense(b, nb=nb)

    @jax.jit
    def potrs(F, B):
        return st.potrs(F, B).data

    @jax.jit
    def recipe(F, B):
        f = F.dense_canonical()
        fwd = jnp.tril(f) if lower else jnp.triu(f).T
        y = blocked.trsm_rec(unit_pad_diag(fwd, n, n),
                             1.0 * B.dense_canonical(), lower=True,
                             prec=PREC, base=nb)
        bwd = jnp.tril(f).T if lower else jnp.triu(f)
        return blocked.trsm_rec(unit_pad_diag(bwd, n, n), 1.0 * y,
                                lower=False, prec=PREC, base=nb)

    x = np.asarray(potrs(F, B))
    np.testing.assert_array_equal(x, np.asarray(recipe(F, B)))
    assert np.abs(a @ x[:n, :NRHS] - b).max() < 1e-10
