"""Round-6 factorization fast-path tests.

Covers the two composed mechanisms of the round-6 rework:

(a) PIVOT-FUSED LU trailing updates — the per-level row permutation is
    folded into the trailing-update gemm reads (gather-as-you-read +
    deferred left swaps, linalg/lu.py) instead of materializing a
    full-width permuted copy per level. Guarded here by the LU contract
    against a float64 numpy reference across dtypes and the 8-device
    mesh, and by an HLO-level assertion that NO gather in the lowered
    program materializes a full-width row block.

(b) IN-PLACE ITERATIVE outer loops at large n for potrf (and the same
    recipe in geqrf) — trailing updates written slab-wise via
    dynamic_update_slice (blocked.herk_trailing_inplace), no per-level
    concatenation copies, with the Pallas tile/panel kernels as the
    base at every step. Guarded by dispatch-policy probes (the
    n=16384/nb=1024 headline shape must route to the iterative loop
    without compiling anything; the 2×2 recursion takes nt beyond the
    HLO-size guard), HLO assertions (dynamic-update-slice present, no
    full-matrix concatenate), reassociation-tolerance parity against
    the recursion, and a wiring check that the Pallas bases sit on the
    default dispatch when a TPU backend is present.
"""

import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import slate_tpu as st
from slate_tpu.core.types import MethodLU, Options, Uplo
from slate_tpu.linalg import cholesky as chol_mod
from slate_tpu.linalg import lu as lu_mod
from slate_tpu.matgen import random_spd
from slate_tpu.ops import blocked, pallas_ops

RNG = np.random.default_rng(61)


def _randn(m, n, dtype):
    a = RNG.standard_normal((m, n))
    if np.issubdtype(np.dtype(dtype), np.complexfloating):
        a = a + 1j * RNG.standard_normal((m, n))
    return np.asarray(a, dtype)


def _check_lu(a, LU, perm, nb, bound=10.0):
    """The getrf contract against a float64 numpy reference: ``perm`` is
    a permutation, ‖A[perm] − L·U‖₁ / (n·ε·‖A‖₁) < ``bound``, and a
    solve through the factors meets the tester's scaled residual
    ‖b − A·x‖₁ / (n·ε·‖A‖₁·‖x‖₁) < 30, ε of the factor's dtype."""
    n = a.shape[0]
    p = np.asarray(perm)
    assert np.array_equal(np.sort(p), np.arange(len(p)))
    # padded rows never pivot into a logical column
    assert np.array_equal(np.sort(p[:n]), np.arange(n))
    a64 = a.astype(np.complex128)
    lu = LU.to_numpy().astype(np.complex128)
    lower = np.tril(lu, -1) + np.eye(n)
    upper = np.triu(lu)
    eps = np.finfo(a.dtype).eps
    anorm = np.linalg.norm(a64, 1)
    err = np.linalg.norm(a64[p[:n]] - lower @ upper, 1) / (n * eps * anorm)
    assert err < bound, err
    b = _randn(n, 3, a.dtype)
    x = st.getrs(LU, perm, st.from_dense(b, nb=nb)).to_numpy()
    x64 = x.astype(np.complex128)
    res = (np.linalg.norm(b - a64 @ x64, 1, axis=0)
           / (n * eps * anorm * np.linalg.norm(x64, 1, axis=0)))
    assert res.max() < 30.0, res


# -- (a) pivot fusion: the LU contract --------------------------------------

@pytest.mark.parametrize("dtype,n,nb", [
    (np.float32, 96, 32),
    # the ragged 136 arm (~7 s, its own padded-shape compile) rides
    # the slow lane (round-22 tier-1 budget); ragged/pad isolation
    # stays pinned by test_uneven_grid.py
    pytest.param(np.float32, 136, 32, marks=pytest.mark.slow),
    (np.float64, 64, 32),  # 2 panels: trailing + suffix fix-up both hit
    (np.complex64, 64, 32), (np.complex128, 64, 32),
])
def test_getrf_pivot_fusion_bit_identical(dtype, n, nb):
    """The fused factors (row permutation read inside the trailing
    update, L columns reordered once at the end) satisfy A[perm] = L·U
    and solve through getrs."""
    a = _randn(n, n, dtype)
    LU, perm, info = st.getrf(st.from_dense(a, nb=nb))
    assert int(info) == 0
    _check_lu(a, LU, perm, nb)


@pytest.mark.parametrize("dtype", [
    # f32 arm (~10 s) rides the slow lane (round-10 headroom); the
    # f64 arm (~11 s) follows in round 22 — tntpiv numerics stay
    # pinned by test_lu.py::test_getrf_tntpiv
    pytest.param(np.float32, marks=pytest.mark.slow),
    pytest.param(np.float64, marks=pytest.mark.slow)])
def test_getrf_tntpiv_pivot_fusion_bit_identical(dtype):
    """Same contract for the CALU/tournament driver (tournament growth
    is weaker than partial pivoting's: a looser factor bound)."""
    n, nb = 128, 32
    a = _randn(n, n, dtype)
    LU, perm, info = st.getrf(st.from_dense(a, nb=nb),
                              Options(method_lu=MethodLU.CALU))
    assert int(info) == 0
    _check_lu(a, LU, perm, nb, bound=100.0)


def test_getrf_threshold_pivot_fusion_bit_identical():
    """And for the PivotThreshold (tournament-panel) arm of the
    iterative loop."""
    n, nb = 96, 32
    a = _randn(n, n, np.float64)
    LU, perm, info = st.getrf(st.from_dense(a, nb=nb),
                              Options(pivot_threshold=0.5))
    assert int(info) == 0
    _check_lu(a, LU, perm, nb, bound=100.0)


def test_gesv_getrs_through_fused_factors():
    """getrs/gesv threaded through the fused factors solve correctly
    (the b[perm] gather of getrs reads the total permutation)."""
    n, nb, nrhs = 128, 32, 4
    a = _randn(n, n, np.float64)
    b = _randn(n, nrhs, np.float64)
    A, B = st.from_dense(a, nb=nb), st.from_dense(b, nb=nb)
    Xf, inf_f = st.gesv(A, B)
    assert int(inf_f) == 0
    res = np.abs(a @ np.asarray(Xf.to_numpy()) - b).max() / (
        np.linalg.norm(a, 1) * np.finfo(np.float64).eps * n)
    assert res < 30.0
    # trans solve through the fused factor
    LU, perm, _ = st.getrf(A)
    Xt = lu_mod.getrs(LU, perm, B, trans=True)
    np.testing.assert_allclose(np.asarray(Xt.to_numpy()),
                               np.linalg.solve(a.T, b),
                               rtol=1e-9, atol=1e-10)


@pytest.mark.slow
def test_getrf_pivot_fusion_bit_identical_mesh(grid2x4):
    """The fused factors keep the LU contract on the 8-device mesh (the
    deferred-left-swap suffix gathers become collective traffic there),
    and the mesh result matches the 1×1 grid. Slow (round-20 tier-1
    budget: two n=256 8-device factor compiles). Tier-1 siblings:
    test_distribution.py::test_mesh_getrf_small and
    test_grid_matches_single_device[getrf]."""
    n, nb = 256, 32
    a = _randn(n, n, np.float64)
    LUf, pf, info = st.getrf(st.from_dense(a, nb=nb, grid=grid2x4))
    assert int(info) == 0
    _check_lu(a, LUf, pf, nb)
    LU1, p1, _ = st.getrf(st.from_dense(a, nb=nb))
    np.testing.assert_array_equal(np.asarray(pf), np.asarray(p1))
    np.testing.assert_allclose(np.asarray(LUf.to_numpy()),
                               np.asarray(LU1.to_numpy()),
                               rtol=1e-13, atol=1e-13)


# -- (a) pivot fusion: HLO-level traffic guard ------------------------------

_GATHER_RE = re.compile(
    r'stablehlo\.gather.*->\s*tensor<(\d+)x(\d+)x(f32|f64)>')


N_FW, NB_FW = 192, 64


def _fullwidth_gather_rows(opts, n=N_FW, nb=NB_FW):
    """Row counts of the 2-D gathers in the LOWERED getrf program whose
    result is a FULL-width (npad-column) row block — a materialized
    permuted copy that carries the stored L columns along. Lowered
    (pre-fusion) StableHLO is the right level: the property is
    structural, not an artifact of the backend's fusion decisions."""
    a = RNG.standard_normal((n, n)).astype(np.float32)
    A = st.from_dense(a, nb=nb)

    def f(A):
        return st.getrf(A, opts)[0].data

    txt = jax.jit(f).lower(A).as_text()
    shapes = [(int(m.group(1)), int(m.group(2)))
              for m in _GATHER_RE.finditer(txt)]
    return sorted(rows for rows, w in shapes if w == n)


def test_hlo_getrf_fused_has_no_fullwidth_permuted_copy():
    """The traffic assertion of pivot fusion: getrf's per-level
    trailing update contains NO materialized full-width permuted row
    block. Each level gathers whole rows of its trailing block (the nb
    pivot rows, then the rows below them), so only the first level,
    whose trailing block is the input with its panel's columns, reads
    full-width rows; later levels gather the (n−k0)- or (n−k1)-wide
    trailing block, and the deferred left swaps gather nb-wide blocks."""
    assert _fullwidth_gather_rows(Options()) == [NB_FW, N_FW - NB_FW]


def test_hlo_getrf_tntpiv_fused_has_no_fullwidth_permuted_copy():
    assert _fullwidth_gather_rows(Options(method_lu=MethodLU.CALU)) == []


# -- (b) in-place iterative outer loops -------------------------------------

def test_iter_dispatch_policy_covers_headline_shapes():
    """The dispatch must route the benchmark's shapes (n=16384,
    nb=1024 — and every nt ≤ 64 shape) to the iterative in-place loop;
    the recursion takes only nt past the HLO-size guard. Pure policy
    probe: nothing is compiled."""
    assert chol_mod._iter_eligible(16384, 1024)
    assert lu_mod._iter_eligible(16384, 1024)
    assert chol_mod._iter_eligible(65536, 1024)   # nt = 64, boundary
    assert not chol_mod._iter_eligible(16384, 128)  # nt = 128 > guard
    assert not lu_mod._iter_eligible(16384 + 512, 1024)  # ragged width


def _spy_routes(monkeypatch, mod, names):
    """Count calls of each of ``mod``'s driver functions ``names``,
    keyed by the name's last word (iter / rec)."""
    calls = {name.split("_")[-1]: 0 for name in names}
    for name in names:
        orig = getattr(mod, name)
        key = name.split("_")[-1]

        def spy(*a, _o=orig, _k=key, **kw):
            calls[_k] += 1
            return _o(*a, **kw)

        monkeypatch.setattr(mod, name, spy)
    return calls


def test_potrf_dispatch_routes_to_iter_by_default(monkeypatch):
    calls = _spy_routes(monkeypatch, chol_mod, ("_potrf_iter", "_potrf_rec"))
    a = np.asarray(random_spd(192, dtype=jnp.float64, seed=5))
    A = st.hermitian(np.tril(a), nb=64, uplo=Uplo.Lower)
    st.potrf(A)
    assert calls["iter"] == 1 and calls["rec"] == 0
    # nt = 3 past the HLO-size guard: the recursion takes it
    monkeypatch.setattr(chol_mod, "_ITER_MAX_NT", 2)
    st.potrf(A)
    assert calls["rec"] >= 1


@pytest.mark.parametrize("driver", ["potrf", "getrf"])
@pytest.mark.parametrize("past_guard", [False, True],
                         ids=["nt=max", "nt=max+1"])
def test_factor_dispatch_boundary(monkeypatch, driver, past_guard):
    """The one selection left in each driver, on both sides of it:
    with the HLO-size guard _ITER_MAX_NT at 4, nt = 4 runs the
    iterative loop alone and nt = 5 the 2×2 recursion (whose halves
    the loop then factors); either way the factor matches numpy."""
    nb, max_nt = 16, 4
    n = nb * (max_nt + past_guard)
    mod = chol_mod if driver == "potrf" else lu_mod
    monkeypatch.setattr(mod, "_ITER_MAX_NT", max_nt)
    calls = _spy_routes(monkeypatch, mod,
                        (f"_{driver}_iter", f"_{driver}_rec"))
    if driver == "potrf":
        a = np.asarray(random_spd(n, dtype=jnp.float64, seed=17))
        L, info = st.potrf(st.hermitian(np.tril(a), nb=nb,
                                        uplo=Uplo.Lower))
        np.testing.assert_allclose(np.tril(L.to_numpy()),
                                   np.linalg.cholesky(a),
                                   rtol=1e-10, atol=1e-10)
    else:
        a = _randn(n, n, np.float64)
        LU, perm, info = st.getrf(st.from_dense(a, nb=nb))
        _check_lu(a, LU, perm, nb)
    assert int(info) == 0
    if past_guard:
        assert calls["rec"] >= 1 and calls["iter"] == 2
    else:
        assert calls["iter"] == 1 and calls["rec"] == 0


def test_potrf_iter_matches_recursion_within_reassociation(monkeypatch):
    """The in-place iterative loop reassociates the trailing update
    (slab gemms vs the recursion's split gemms), so the two dispatches
    agree to factorization accuracy, not bitwise. Force the TRUE
    recursion (nt past the guard, crossover to 0 so its iterative base
    case never runs) and compare."""
    n, nb = 128, 32
    a = np.asarray(random_spd(n, dtype=jnp.float64, seed=13))
    A = st.hermitian(np.tril(a), nb=nb, uplo=Uplo.Lower)
    L1, i1 = st.potrf(A)
    monkeypatch.setattr(chol_mod, "_ITER_MAX_NT", 2)
    monkeypatch.setattr(chol_mod, "_POTRF_ITER_BASE", 0)
    L0, i0 = st.potrf(A)
    assert int(i1) == int(i0) == 0
    scale = np.linalg.norm(a, 1) * n * np.finfo(np.float64).eps
    assert np.abs(L1.to_numpy() - L0.to_numpy()).max() < 10 * scale


def test_hlo_potrf_iter_updates_in_place_no_full_concat(monkeypatch):
    """HLO guard: the iterative potrf outer loop updates the trailing
    matrix via dynamic_update_slice and builds NO full-matrix
    concatenation (the recursion's per-level copies). The recursion
    (nt past the guard, crossover forced to 0 so its iterative base
    case never runs) must show the full-size concatenate, proving the
    probe detects it."""
    n, nb = 256, 32
    a = np.asarray(random_spd(n, dtype=jnp.float32, seed=3))
    A = st.hermitian(np.tril(a), nb=nb, uplo=Uplo.Lower)

    def lower_text(opts):
        def f(A):
            return st.potrf(A, opts)[0].data
        return jax.jit(f).lower(A).as_text()

    cat = re.compile(r'stablehlo\.concatenate.*->\s*tensor<'
                     + str(n) + r'x' + str(n) + r'xf32>')
    txt = lower_text(Options())
    assert "stablehlo.dynamic_update_slice" in txt
    assert not cat.search(txt), \
        "default potrf still concatenates a full-size trailing copy"
    monkeypatch.setattr(chol_mod, "_ITER_MAX_NT", 4)
    monkeypatch.setattr(chol_mod, "_POTRF_ITER_BASE", 0)
    rec = lower_text(Options())
    assert cat.search(rec), "probe lost its reference signal"


def test_hlo_geqrf_updates_in_place():
    """geqrf mirrors the recipe: panel + trailing writes are
    dynamic_update_slice into the resident matrix; no full-size
    concatenate appears in the lowered program."""
    m, n, nb = 256, 192, 64
    a = RNG.standard_normal((m, n)).astype(np.float32)
    A = st.from_dense(a, nb=nb)

    def f(A):
        return st.geqrf(A).vr

    txt = jax.jit(f).lower(A).as_text()
    assert "stablehlo.dynamic_update_slice" in txt
    assert not re.search(r'stablehlo\.concatenate.*->\s*tensor<'
                         + str(m) + r'x' + str(n) + r'xf32>', txt)


def test_herk_trailing_inplace_matches_reference():
    """blocked.herk_trailing_inplace == the masked dense update on the
    lower trapezoid (strict upper of the trailing block is untouched
    garbage by contract — compare tril only)."""
    s, k1, nb = 160, 32, 32
    a = RNG.standard_normal((s, s))
    pan = RNG.standard_normal((s - k1, nb))
    out = np.asarray(blocked.herk_trailing_inplace(
        jnp.asarray(a), jnp.asarray(pan), k1, nb))
    ref = a.copy()
    ref[k1:, k1:] -= pan @ pan.T
    np.testing.assert_allclose(np.tril(out[k1:, k1:]),
                               np.tril(ref[k1:, k1:]),
                               rtol=1e-12, atol=1e-12)
    # region above/left of the trailing block is untouched
    np.testing.assert_array_equal(out[:k1, :], a[:k1, :])
    np.testing.assert_array_equal(out[:, :k1], a[:, :k1])


def test_pallas_tile_bases_sit_on_default_dispatch(monkeypatch):
    """Wiring check (CPU host): with a TPU backend reported, the
    eligibility gates admit the bench headline tile/panel shapes, and
    the default potrf dispatch consults the Pallas tile base at EVERY
    panel step of the iterative loop."""
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    assert pallas_ops.chol_eligible(1024, jnp.float32.dtype)
    assert pallas_ops.lu_panel_eligible(8192, 32, jnp.float32.dtype)
    assert pallas_ops.qr_panel_eligible(8192, 32, jnp.float32.dtype)

    # (1) the iterative loop invokes the tile base once PER STEP: spy on
    # the (jit-cached) _tile_chol entry the loop calls eagerly
    steps = {"tile_chol": 0}
    orig_tile = chol_mod._tile_chol

    def spy_tile(akk, _o=orig_tile):
        steps["tile_chol"] += 1
        return _o(akk)

    monkeypatch.setattr(chol_mod, "_tile_chol", spy_tile)
    # (2) the tile base consults the Pallas gate/kernel (trace-time —
    # jit caches mean the consult happens once per shape, so clear it)
    consults = {"eligible": 0}

    def fake_eligible(b, dtype):
        consults["eligible"] += 1
        return True

    def fake_chol_tile(a, **kw):
        # stand-in so the "kernel" path executes on this CPU host
        return jnp.tril(jax.lax.linalg.cholesky(a, symmetrize_input=False))

    monkeypatch.setattr(pallas_ops, "chol_eligible", fake_eligible)
    monkeypatch.setattr(pallas_ops, "chol_tile", fake_chol_tile)
    try:
        orig_tile.clear_cache()
    except AttributeError:
        pass
    try:
        n, nb = 256, 64
        a = np.asarray(random_spd(n, dtype=jnp.float32, seed=21))
        A = st.hermitian(np.tril(a), nb=nb, uplo=Uplo.Lower)
        L, info = st.potrf(A)
        assert int(info) == 0
        assert steps["tile_chol"] == n // nb, \
            "iterative loop must hit the tile base at every panel step"
        assert consults["eligible"] >= 1, \
            "tile base never consulted the Pallas gate"
        ln = np.tril(L.to_numpy())
        r = np.linalg.norm(a - ln @ ln.T, 1) / (
            np.linalg.norm(a, 1) * n * np.finfo(np.float32).eps)
        assert r < 30.0
    finally:
        # drop the fake-kernel trace so later tests re-trace the real one
        try:
            orig_tile.clear_cache()
        except AttributeError:
            pass
