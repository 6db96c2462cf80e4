"""Distributed layout + collective-insertion tests (8-device CPU mesh).

VERDICT round-1 items 5/6: the block-cyclic storage mode must be real
(device tile ownership matching the ScaLAPACK map), factorizations must
keep outputs sharded (not silently replicated) and agree with the 1×1
grid bit-for-bit at the logical level, and collective ops must actually
appear in the compiled HLO (no "GSPMD silently replicates" regression).
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import slate_tpu as st
from slate_tpu.core.grid import cyclic_permutation
from slate_tpu.core.types import MethodGemm

RNG = np.random.default_rng(11)


def _spd(n, dtype=np.float64):
    a = RNG.standard_normal((n, n)).astype(dtype)
    return a @ a.T + n * np.eye(n, dtype=dtype)


# -- block-cyclic storage ---------------------------------------------------

def test_cyclic_shard_roundtrip(grid2x4):
    m, n, nb = 144, 208, 16
    a = RNG.standard_normal((m, n))
    A = st.from_dense(a, nb=nb).shard(grid2x4, cyclic=True)
    assert A.cyclic
    np.testing.assert_array_equal(A.to_numpy(), a)
    # re-shard back to contiguous
    B = A.shard(grid2x4)
    assert not B.cyclic
    np.testing.assert_array_equal(B.to_numpy(), a)


def test_cyclic_device_ownership(grid2x4):
    """Device (pi, qi) must hold exactly the ScaLAPACK cyclic tile set
    {(i, j) : i mod p == pi, j mod q == qi}."""
    n, nb = 128, 16
    p, q = grid2x4.p, grid2x4.q
    a = np.arange(n * n, dtype=np.float64).reshape(n, n)
    A = st.from_dense(a, nb=nb).shard(grid2x4, cyclic=True)
    mtp = A.data.shape[0] // nb
    ntp = A.data.shape[1] // nb
    perm_r = cyclic_permutation(mtp, p)
    perm_c = cyclic_permutation(ntp, q)
    for shard in A.data.addressable_shards:
        r0, c0 = shard.index[0].start or 0, shard.index[1].start or 0
        local = np.asarray(shard.data)
        # every tile in this shard must be a cyclic-owned logical tile
        for it in range(local.shape[0] // nb):
            for jt in range(local.shape[1] // nb):
                gi = perm_r[r0 // nb + it]
                gj = perm_c[c0 // nb + jt]
                np.testing.assert_array_equal(
                    local[it * nb:(it + 1) * nb, jt * nb:(jt + 1) * nb],
                    a[gi * nb:(gi + 1) * nb, gj * nb:(gj + 1) * nb])
                # and ownership must follow the ScaLAPACK map
                dev_row = (r0 // nb) // (mtp // p)
                dev_col = (c0 // nb) // (ntp // q)
                assert gi % p == dev_row and gj % q == dev_col


@pytest.mark.slow  # ~14 s 3-factorization sweep (round-10 headroom);
# mesh correctness stays pinned by test_grid_matches_single_device
def test_factorizations_accept_cyclic_input(grid2x4):
    n, nb = 192, 16
    a = _spd(n)
    rhs = RNG.standard_normal((n, 3))
    A1 = st.hermitian(np.tril(a), nb=nb, uplo=st.Uplo.Lower)
    Ac = st.hermitian(np.tril(a), nb=nb, uplo=st.Uplo.Lower).shard(
        grid2x4, cyclic=True)
    X1, i1 = st.posv(A1, st.from_dense(rhs, nb=nb))
    Xc, ic = st.posv(Ac, st.from_dense(rhs, nb=nb, grid=grid2x4))
    assert int(i1) == int(ic) == 0
    np.testing.assert_allclose(Xc.to_numpy(), X1.to_numpy(), rtol=1e-12,
                               atol=1e-12)


# -- sharded outputs + 1x1-grid agreement ----------------------------------

# getrf/geqrf arms ride the slow lane (round-20 tier-1 budget: each is
# its own n=256 mesh factor compile); the potrf arm keeps the
# outputs-stay-sharded contract tier-1, and grid_matches_single_device
# pins mesh correctness for all three routines
@pytest.mark.parametrize("routine", [
    "potrf",
    pytest.param("getrf", marks=pytest.mark.slow),
    pytest.param("geqrf", marks=pytest.mark.slow),
])
def test_factorization_outputs_stay_sharded(grid2x4, routine):
    n, nb = 256, 32
    if routine == "potrf":
        a = _spd(n)
        A = st.hermitian(np.tril(a), nb=nb, uplo=st.Uplo.Lower,
                         grid=grid2x4)
        out, _ = st.potrf(A)
        data = out.data
    elif routine == "getrf":
        a = RNG.standard_normal((n, n))
        A = st.from_dense(a, nb=nb, grid=grid2x4)
        out, _, _ = st.getrf(A)
        data = out.data
    else:
        a = RNG.standard_normal((n + 64, n))
        A = st.from_dense(a, nb=nb, grid=grid2x4)
        qr = st.geqrf(A)
        data = qr.vr
    assert len(data.sharding.device_set) == 8, \
        f"{routine}: output collapsed to {data.sharding.device_set}"
    assert not data.sharding.is_fully_replicated, \
        f"{routine}: output silently replicated"


@pytest.mark.parametrize("routine", [
    "potrf",
    # the getrf arm (~9 s) rides the slow lane (round-10 headroom):
    # mesh getrf stays pinned by the nb=64 perm-regression test and
    # test_mesh_getrf_small; the geqrf arm
    # (~12 s, its own n=256 mesh factor compile) follows in round 22 —
    # mesh geqrf stays pinned by test_qr.py::test_geqrf_jit_and_grid
    pytest.param("getrf", marks=pytest.mark.slow),
    pytest.param("geqrf", marks=pytest.mark.slow)])
def test_grid_matches_single_device(grid2x4, routine):
    n, nb = 256, 32
    if routine == "potrf":
        a = _spd(n)
        M1 = st.hermitian(np.tril(a), nb=nb, uplo=st.Uplo.Lower)
        Mg = st.hermitian(np.tril(a), nb=nb, uplo=st.Uplo.Lower,
                          grid=grid2x4)
        r1 = st.potrf(M1)[0].to_numpy()
        rg = st.potrf(Mg)[0].to_numpy()
    elif routine == "getrf":
        a = RNG.standard_normal((n, n))
        r1 = st.getrf(st.from_dense(a, nb=nb))[0].to_numpy()
        rg = st.getrf(st.from_dense(a, nb=nb, grid=grid2x4))[0].to_numpy()
    else:
        a = RNG.standard_normal((n + 64, n))
        r1 = st.geqrf(st.from_dense(a, nb=nb)).vr
        rg = st.geqrf(st.from_dense(a, nb=nb, grid=grid2x4)).vr
        r1, rg = np.asarray(r1), np.asarray(rg)
    np.testing.assert_allclose(rg, r1, rtol=1e-13, atol=1e-13)


# -- collective insertion asserted on compiled HLO --------------------------

_COLLECTIVES = ("all-gather", "all-reduce", "collective-permute",
                "reduce-scatter", "all-to-all")


def _collective_count(fn, *args):
    txt = jax.jit(fn).lower(*args).compile().as_text()
    return sum(txt.count(c) for c in _COLLECTIVES)


def test_hlo_gemm_has_collectives(grid2x4):
    n, nb = 128, 16
    A = st.from_dense(RNG.standard_normal((n, n)), nb=nb, grid=grid2x4)
    B = st.from_dense(RNG.standard_normal((n, n)), nb=nb, grid=grid2x4)
    C = st.from_dense(np.zeros((n, n)), nb=nb, grid=grid2x4)

    def f(A, B, C):
        return st.gemm(1.0, A, B, 0.0, C).data

    assert _collective_count(f, A, B, C) > 0


def test_hlo_potrf_has_collectives(grid2x4):
    # shares ONE mesh-potrf compile with the two schedule tests below
    # (_scheduled_potrf_entry caches it — the compile is ~40 s here)
    hlo, _ = _scheduled_potrf_entry(grid2x4)
    assert sum(hlo.count(c) for c in _COLLECTIVES) > 0, \
        "potrf compiled without any collective: GSPMD replicated the work"


def test_hlo_hemm_trsm_have_collectives(grid2x4):
    n, nb = 128, 16
    a = _spd(n)
    A = st.hermitian(np.tril(a), nb=nb, uplo=st.Uplo.Lower, grid=grid2x4)
    L = st.triangular(np.tril(a), nb=nb, uplo=st.Uplo.Lower, grid=grid2x4)
    B = st.from_dense(RNG.standard_normal((n, n)), nb=nb, grid=grid2x4)

    def f_hemm(A, B):
        return st.hemm(st.Side.Left, 1.0, A, B, 0.0, B).data

    def f_trsm(L, B):
        return st.trsm(st.Side.Left, 1.0, L, B).data

    assert _collective_count(f_hemm, A, B) > 0
    assert _collective_count(f_trsm, L, B) > 0


def test_hlo_rank_k_family_has_collectives(grid2x4):
    """VERDICT r2 weak #8: syrk/herk/syr2k/her2k must carry the same grid
    constraints as gemm so standalone trailing-update calls shard rather
    than replicate (reference src/internal/internal_herk.cc)."""
    n, k, nb = 128, 64, 16
    a = RNG.standard_normal((n, k))
    b = RNG.standard_normal((n, k))
    spd = _spd(n)
    A = st.from_dense(a, nb=nb, grid=grid2x4)
    B = st.from_dense(b, nb=nb, grid=grid2x4)
    Ch = st.hermitian(np.tril(spd), nb=nb, uplo=st.Uplo.Lower, grid=grid2x4)
    Cs = st.symmetric(np.tril(spd), nb=nb, uplo=st.Uplo.Lower, grid=grid2x4)

    def f_herk(A, C):
        return st.herk(-1.0, A, 1.0, C).data

    def f_syrk(A, C):
        return st.syrk(-1.0, A, 1.0, C).data

    def f_her2k(A, B, C):
        return st.her2k(-1.0, A, B, 1.0, C).data

    def f_syr2k(A, B, C):
        return st.syr2k(-1.0, A, B, 1.0, C).data

    assert _collective_count(f_herk, A, Ch) > 0, "herk replicated"
    assert _collective_count(f_syrk, A, Cs) > 0, "syrk replicated"
    assert _collective_count(f_her2k, A, B, Ch) > 0, "her2k replicated"
    assert _collective_count(f_syr2k, A, B, Cs) > 0, "syr2k replicated"

    # outputs stay sharded and match the 1x1 grid
    out = st.herk(-1.0, A, 1.0, Ch)
    assert not out.data.sharding.is_fully_replicated
    ref = st.herk(-1.0, st.from_dense(a, nb=nb),
                  1.0, st.hermitian(np.tril(spd), nb=nb, uplo=st.Uplo.Lower))
    np.testing.assert_allclose(out.to_numpy(), ref.to_numpy(),
                               rtol=1e-12, atol=1e-12)


def test_mesh_getrf_small(grid2x4):
    """Tier-1 sibling of the slow mesh getrf cases: getrf on the 2×4
    mesh through the GSPMD panel (the panel operand replicated, XLA's
    own collectives) at one (256, 32) panel — A[perm] = L·U against
    numpy, and collectives present in the compiled program."""
    m, w, nb = 256, 32, 32
    a = np.random.default_rng(22).standard_normal((m, w))
    A = st.from_dense(a, nb=nb, grid=grid2x4)
    LU, perm, info = st.getrf(A)
    assert int(info) == 0
    lu, perm = LU.to_numpy(), np.asarray(perm)
    assert np.array_equal(np.sort(perm), np.arange(len(perm)))
    L = np.tril(lu, -1) + np.eye(m, w)
    U = np.triu(lu[:w])
    assert np.abs(a[perm[:m]] - L @ U).max() < 1e-12
    assert _collective_count(lambda A: st.getrf(A)[0].data, A) > 0, \
        "mesh getrf compiled without collectives"


# -- P3 static evidence: scheduled-HLO collective/compute interleaving ------

import re


_SCHED_CACHE = {}


def _scheduled_potrf_entry(grid, n=256, nb=32):
    """Scheduled HLO (post-optimization, is_scheduled=true) of mesh
    potrf's entry computation, line-classified: 'C' collective,
    'X' compute (fusion/dot/custom-call). The compile is cached across
    the two schedule tests — it is the expensive part."""
    if (n, nb) in _SCHED_CACHE:
        return _SCHED_CACHE[(n, nb)]
    a = _spd(n)
    A = st.hermitian(np.tril(a), nb=nb, uplo=st.Uplo.Lower, grid=grid)

    def f(A):
        return st.potrf(A)[0].data

    hlo = jax.jit(f).lower(A).compile().as_text()
    m = re.search(r"^ENTRY [^{]*\{(.*?)^\}", hlo, re.S | re.M)
    assert m, "no ENTRY computation in compiled HLO"
    coll = ("all-gather", "all-reduce", "collective-permute",
            "reduce-scatter", "all-to-all")
    comp = ("fusion(", " dot(", "custom-call(", "convolution(")
    seq = []
    for ln in m.group(1).splitlines():
        if any(c + "(" in ln or c + "-start(" in ln or c + "-done(" in ln
               for c in coll):
            seq.append("C")
        elif any(c in ln for c in comp):
            seq.append("X")
    _SCHED_CACHE[(n, nb)] = (hlo, seq)
    return hlo, seq


def test_mesh_potrf_schedule_interleaves_collectives_with_updates(grid2x4):
    """VERDICT r5 'Missing #6' / ISSUE 2 P3 static evidence: in mesh
    potrf's SCHEDULED HLO, collective ops must be interleaved with the
    trailing-update compute (fusions/dots) throughout the instruction
    stream — the compiler-scheduled analog of the reference's lookahead
    (panel broadcast overlapping trailing work, src/potrf.cc:84-195) —
    rather than clumped into a prologue/epilogue. The 8-step n=256
    factorization must show at least 2·nt separate collective runs
    embedded in compute."""
    n, nb = 256, 32
    hlo, seq = _scheduled_potrf_entry(grid2x4, n, nb)
    assert "is_scheduled=true" in hlo, "compiled module is not scheduled"
    ncoll = seq.count("C")
    ncomp = seq.count("X")
    assert ncoll > 0 and ncomp > 0
    runs = sum(1 for i, s in enumerate(seq)
               if s == "C" and (i == 0 or seq[i - 1] != "C"))
    assert runs >= 2 * (n // nb), (
        f"collectives clumped: {ncoll} collectives in only {runs} runs "
        f"against {ncomp} compute ops")


def test_mesh_potrf_async_collective_start_done_interleaving(grid2x4):
    """The stronger TPU-shaped assertion: async collective start/done
    pairs with independent trailing-update compute scheduled BETWEEN
    start and done (true latency hiding). XLA:CPU lowers collectives
    synchronously (zero *-start/done pairs — verified in
    PERF_HISTORY.md round 4), so this skips off-TPU and runs on a
    TPU-attached session."""
    hlo, _ = _scheduled_potrf_entry(grid2x4)
    starts = re.findall(r"%(\S*?(?:all-gather|all-reduce|"
                        r"collective-permute)-start\S*)\s*=", hlo)
    if not starts:
        pytest.skip("backend lowers collectives synchronously (no "
                    "async start/done pairs in scheduled HLO); the "
                    "interleaving assertion needs a TPU backend")
    m = re.search(r"^ENTRY [^{]*\{(.*?)^\}", hlo, re.S | re.M)
    lines = m.group(1).splitlines()
    hidden = 0
    open_since = {}  # start instruction NAME -> schedule index
    for i, ln in enumerate(lines):
        if "-start(" in ln and "=" in ln:
            open_since[ln.split("=")[0].strip().lstrip("%")] = i
        elif "-done(" in ln:
            # a done op references ITS start by name as an operand;
            # the (?!\d) guard keeps %op.1 from matching %op.10
            for sname, j in list(open_since.items()):
                if re.search(re.escape(sname) + r"(?!\d)", ln):
                    if any("fusion(" in s or " dot(" in s
                           for s in lines[j + 1:i]):
                        hidden += 1
                    open_since.pop(sname)
                    break
    assert hidden > 0, ("no compute scheduled inside any async "
                        "collective start/done window")


# -- explicit SUMMA routing -------------------------------------------------

def test_method_gemm_summa_routing(grid2x4):
    n, nb = 128, 16
    a = RNG.standard_normal((n, n))
    b = RNG.standard_normal((n, n))
    c = RNG.standard_normal((n, n))
    A = st.from_dense(a, nb=nb, grid=grid2x4)
    B = st.from_dense(b, nb=nb, grid=grid2x4)
    C = st.from_dense(c, nb=nb, grid=grid2x4)
    out = st.gemm(2.0, A, B, -1.0, C,
                  st.Options(method_gemm=MethodGemm.SUMMA))
    np.testing.assert_allclose(out.to_numpy(), 2.0 * a @ b - c,
                               rtol=1e-10, atol=1e-10)


@pytest.mark.slow
def test_hlo_he2hb_has_collectives_and_heev_2stage_runs(grid2x4):
    """VERDICT r4 weak #7: the two-stage heev's stage-1 (he2hb) exists
    for its mesh sharding — assert its compiled HLO actually carries
    collectives on the 2x4 grid, and run the full two-stage eigensolver
    on the mesh end to end. Slow (round-20 tier-1 budget: the full
    n=256 2x4 two-stage pipeline compile). Tier-1 sibling:
    test_spectral.py::test_mesh_census_collective_bytes pins nonzero
    collective bytes for the staged he2hb on a 2x2 grid through the
    Session census."""
    from slate_tpu.core.types import MethodEig, Options

    n, nb = 256, 32
    a = _spd(n)
    A = st.hermitian(np.tril(a), nb=nb, uplo=st.Uplo.Lower, grid=grid2x4)

    def f_stage1(A):
        band, refl = st.he2hb(A)
        return band.data

    assert _collective_count(f_stage1, A) > 0, \
        "he2hb compiled without any collective: stage-1 replicated"

    w, Z = st.heev(A, Options(method_eig=MethodEig.DC,
                              eig_stage1="two_stage"))
    z = np.asarray(Z.to_numpy(), np.float64)
    wn = np.asarray(w, np.float64)
    res = np.abs(a @ z - z * wn[None, :]).max() / max(np.abs(wn).max(), 1)
    orth = np.abs(z.T @ z - np.eye(n)).max()
    assert res < 5e-5 and orth < 5e-5, (res, orth)
