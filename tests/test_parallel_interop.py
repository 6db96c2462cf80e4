"""Collectives layer, explicit SUMMA, and ScaLAPACK/native interop tests.

Reference analogs: the comm layer property tests SURVEY §7.3 calls for
(shard_map collectives vs single-device reference on the virtual CPU
mesh — replacing the reference's `mpirun -np 4` testing), plus
unit-level checks of the scalapack_api-style interchange.
"""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax import shard_map
from jax.sharding import PartitionSpec as P

import slate_tpu as st
from slate_tpu.core.grid import ProcessGrid, ROW_AXIS, COL_AXIS
from slate_tpu.parallel import (bcast_from, gemm_summa, maxloc, reduce_sum,
                                ring_shift)
from slate_tpu.interop import (bc_pack, bc_unpack, from_lapack,
                               from_scalapack, have_native, tile_pack,
                               tile_unpack, to_scalapack)

RNG = np.random.default_rng(77)


def _mesh1d(devices):
    import numpy as onp
    from jax.sharding import Mesh
    return Mesh(onp.asarray(devices[:8]).reshape(8), ("x",))


def test_bcast_from(devices):
    mesh = _mesh1d(devices)
    x = jnp.arange(8.0 * 4).reshape(8, 4)

    @functools.partial(shard_map, mesh=mesh, in_specs=P("x", None),
                       out_specs=P("x", None))
    def f(blk):
        return bcast_from(blk, 3, "x")

    out = np.asarray(f(x))
    for i in range(8):
        np.testing.assert_array_equal(out[i], np.asarray(x)[3])


def test_reduce_and_maxloc(devices):
    mesh = _mesh1d(devices)
    vals = jnp.asarray(RNG.standard_normal((8, 5)))

    @functools.partial(shard_map, mesh=mesh, in_specs=P("x", None),
                       out_specs=P("x", None))
    def f(blk):
        s = reduce_sum(blk, "x")
        gmax, owner, idx = maxloc(jnp.abs(blk[0]), "x")
        return jnp.concatenate(
            [s[0], gmax[None], owner.astype(s.dtype)[None],
             idx.astype(s.dtype)[None]])[None]

    out = np.asarray(f(vals))
    np.testing.assert_allclose(out[0, :5], np.asarray(vals).sum(0),
                               rtol=1e-12)
    flat = np.abs(np.asarray(vals))
    o, i = np.unravel_index(np.argmax(flat), flat.shape)
    assert out[0, 5] == pytest.approx(flat[o, i])
    assert int(out[0, 6]) == o and int(out[0, 7]) == i


def test_ring_shift(devices):
    mesh = _mesh1d(devices)
    x = jnp.arange(8.0)[:, None]

    @functools.partial(shard_map, mesh=mesh, in_specs=P("x", None),
                       out_specs=P("x", None))
    def f(blk):
        return ring_shift(blk, "x", 1)

    out = np.asarray(f(x)).ravel()
    np.testing.assert_array_equal(out, np.roll(np.arange(8.0), 1))


@pytest.mark.parametrize("shape", [(64, 64, 64), (64, 48, 80)])
def test_gemm_summa(grid2x2, shape):
    m, n, k = shape
    a = RNG.standard_normal((m, k))
    b = RNG.standard_normal((k, n))
    c = RNG.standard_normal((m, n))
    A = st.from_dense(a, nb=16, grid=grid2x2)
    B = st.from_dense(b, nb=16, grid=grid2x2)
    C = st.from_dense(c, nb=16, grid=grid2x2)
    out = gemm_summa(1.5, A, B, -0.5, C)
    np.testing.assert_allclose(out.to_numpy(), 1.5 * a @ b - 0.5 * c,
                               rtol=1e-10, atol=1e-10)


def test_gemm_summa_rect_grid(grid2x4):
    m, n, k = 64, 64, 64
    a = RNG.standard_normal((m, k))
    b = RNG.standard_normal((k, n))
    A = st.from_dense(a, nb=8, grid=grid2x4)
    B = st.from_dense(b, nb=8, grid=grid2x4)
    C = st.from_dense(np.zeros((m, n)), nb=8, grid=grid2x4)
    out = gemm_summa(1.0, A, B, 0.0, C)
    np.testing.assert_allclose(out.to_numpy(), a @ b, rtol=1e-10,
                               atol=1e-10)


# -- interop ---------------------------------------------------------------

def test_native_lib_available():
    assert have_native()  # g++ is in the image; the build must succeed


def test_bc_pack_unpack_all_ranks():
    m, n, nb, p, q = 45, 61, 8, 3, 2
    a = RNG.standard_normal((m, n))
    out = np.zeros((m, n))
    for pi in range(p):
        for qi in range(q):
            out = bc_unpack(bc_pack(a, nb, p, q, pi, qi), m, n, nb, p, q,
                            pi, qi, out=out)
    np.testing.assert_array_equal(out, a)


def test_bc_pack_matches_scalapack_definition():
    """bc_pack must produce byte-compatible ScaLAPACK local arrays:
    column-major (numroc × numroc) with the INDXG2P/INDXG2L index maps
    (ScaLAPACK TOOLS; reference wraps such buffers zero-copy in
    Matrix::fromScaLAPACK, include/slate/Matrix.hh:347)."""
    from slate_tpu.interop import numroc
    m, n, nb, p, q = 45, 61, 8, 3, 2
    a = RNG.standard_normal((m, n))
    for pi in range(p):
        for qi in range(q):
            loc = bc_pack(a, nb, p, q, pi, qi)
            assert loc.shape == (numroc(m, nb, pi, p), numroc(n, nb, qi, q))
            assert loc.flags.f_contiguous or 1 in loc.shape
            for gi in range(m):
                for gj in range(n):
                    if (gi // nb) % p == pi and (gj // nb) % q == qi:
                        li = (gi // nb // p) * nb + gi % nb  # INDXG2L − 1
                        lj = (gj // nb // q) * nb + gj % nb
                        assert loc[li, lj] == a[gi, gj]


def test_bc_unpack_flat_with_lld_slack():
    """A flat BLACS buffer with lld > mloc (descriptor LLD_ slack) must
    unpack identically to the exact-size array."""
    from slate_tpu.interop import numroc
    m, n, nb, p, q, pi, qi = 40, 24, 8, 2, 2, 1, 0
    a = RNG.standard_normal((m, n))
    loc = bc_pack(a, nb, p, q, pi, qi)
    mloc, nloc = loc.shape
    lld = mloc + 5
    padded = np.zeros((lld, nloc))
    padded[:mloc] = loc
    out = bc_unpack(padded.ravel(order="F"), m, n, nb, p, q, pi, qi,
                    lld=lld)
    ref = bc_unpack(loc, m, n, nb, p, q, pi, qi)
    np.testing.assert_array_equal(out, ref)


def test_tile_pack_unpack():
    m, n, nb = 37, 29, 8
    a = RNG.standard_normal((m, n))
    t = tile_pack(a, nb)
    assert t.shape == (-(-m // nb), -(-n // nb), nb, nb)
    np.testing.assert_array_equal(tile_unpack(t, m, n), a)
    # tile content spot check
    np.testing.assert_array_equal(t[1, 2, :8, :8], a[8:16, 16:24])


def test_from_to_scalapack_roundtrip(grid2x2):
    m, n, nb, p, q = 40, 56, 8, 2, 2
    a = RNG.standard_normal((m, n))
    A = st.from_dense(a, nb=nb)
    locals_ = to_scalapack(A, p, q)
    B = from_scalapack(locals_, m, n, nb, p, q, grid=grid2x2)
    np.testing.assert_array_equal(B.to_numpy(), a)
    # solve through the interop path end-to-end (scalapack_api analog)
    spd = np.asarray(st.matgen.random_spd(32, dtype=jnp.float64, seed=1))
    S = st.hermitian(np.tril(spd), nb=8, uplo=st.Uplo.Lower)
    locs = to_scalapack(S, p, q)
    S2 = from_scalapack(locs, 32, 32, 8, p, q)
    S2 = st.hermitian(S2.to_numpy(), nb=8, uplo=st.Uplo.Lower)
    rhs = RNG.standard_normal((32, 2))
    X, info = st.posv(S2, st.from_dense(rhs, nb=8))
    np.testing.assert_allclose(X.to_numpy(), np.linalg.solve(spd, rhs),
                               rtol=1e-8)


def test_from_lapack():
    m, n = 20, 12
    a = np.asfortranarray(RNG.standard_normal((m, n)))
    A = from_lapack(a, nb=8)
    np.testing.assert_array_equal(A.to_numpy(), a)


@pytest.mark.parametrize("dtype", [np.float32, np.complex64,
                                   np.complex128])
def test_bc_pack_unpack_multiprecision(dtype):
    """Round 5: the native block-cyclic packers are element-size
    templated — s/c/z round-trip exactly (byte-compatible with the f64
    golden path's layout)."""
    from slate_tpu.interop import bc_pack, bc_unpack

    rng = np.random.default_rng(17)
    m, n, nb, p, q = 37, 29, 8, 2, 3
    a = rng.standard_normal((m, n)).astype(dtype)
    if np.issubdtype(dtype, np.complexfloating):
        a = a + 1j * rng.standard_normal((m, n)).astype(a.real.dtype)
    out = np.zeros((m, n), dtype)
    for pi in range(p):
        for qi in range(q):
            loc = bc_pack(a, nb, p, q, pi, qi)
            assert loc.dtype == np.dtype(dtype)
            bc_unpack(loc, m, n, nb, p, q, pi, qi, out=out)
    np.testing.assert_array_equal(out, a)


def test_bc_pack_f32_matches_f64_layout():
    """Same values packed as f32 and f64 land in the same slots (the
    esize-generic kernel preserves the golden-path layout)."""
    from slate_tpu.interop import bc_pack

    rng = np.random.default_rng(18)
    m, n, nb, p, q = 23, 31, 4, 3, 2
    a64 = np.round(rng.standard_normal((m, n)) * 8) / 8  # f32-exact
    a32 = a64.astype(np.float32)
    for pi in range(p):
        for qi in range(q):
            l64 = bc_pack(a64, nb, p, q, pi, qi)
            l32 = bc_pack(a32, nb, p, q, pi, qi)
            np.testing.assert_array_equal(l32.astype(np.float64), l64)


@pytest.mark.parametrize("dtype", [np.float32, np.complex128])
def test_tile_pack_unpack_multiprecision(dtype):
    from slate_tpu.interop import tile_pack, tile_unpack

    rng = np.random.default_rng(19)
    m, n, nb = 21, 13, 8
    a = rng.standard_normal((m, n)).astype(dtype)
    t = tile_pack(a, nb)
    assert t.dtype == np.dtype(dtype)
    np.testing.assert_array_equal(tile_unpack(t, m, n), a)


def test_scalapack_roundtrip_complex(grid2x2):
    """from_scalapack/to_scalapack keep complex dtypes end to end
    (lifts the r4 f64-only restriction, VERDICT missing #4)."""
    import slate_tpu as st
    from slate_tpu.interop import from_scalapack, to_scalapack

    rng = np.random.default_rng(20)
    m, n, nb = 24, 20, 8
    a = (rng.standard_normal((m, n))
         + 1j * rng.standard_normal((m, n))).astype(np.complex64)
    A = st.from_dense(a, nb=nb)
    locals_ = to_scalapack(A, 2, 2)
    assert all(l.dtype == np.complex64 for l in locals_)
    B = from_scalapack(locals_, m, n, nb, 2, 2)
    np.testing.assert_array_equal(np.asarray(B.to_numpy()), a)


def test_tester_origin_scalapack_complex():
    """tester --origin scalapack now runs complex dtypes (r4 raised)."""
    from slate_tpu.tester import Ctx

    ctx = Ctx(m=20, n=20, nb=8, grid=None, dtype=np.complex64, seed=1,
              iters=1, origin="scalapack")
    rng = np.random.default_rng(21)
    a = (rng.standard_normal((20, 20))
         + 1j * rng.standard_normal((20, 20))).astype(np.complex64)
    out = ctx.origin_array(a)
    np.testing.assert_array_equal(np.asarray(out), a)
