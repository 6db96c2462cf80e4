"""Round-7 lookahead-pipeline + batched-CALU + mesh-perm tests (ISSUE 3).

Covers:

(a) LOOKAHEAD-1 PIPELINE (Options.lookahead, default 1) — at step k the
    trailing update is split at the next-panel slab and panel k+1 is
    factored between the slab and the remainder, so the serial panel
    chain of step k+1 carries NO data edge to step k's remainder gemms.
    Guarded by: bit-identity lookahead=1 vs lookahead=0 across dtypes
    and the 8-device mesh (the ops are identical — only the order of
    independent ops changes, and gemm column splits leave each output
    element's contraction unchanged); a JAXPR dependence probe proving
    the decoupling structurally (with the sequential arm as the
    positive control); and a scheduled-HLO interleaving guard that
    needs a backend whose scheduler actually reorders (skips on CPU,
    like test_distribution's async-collective test).

(b) BATCHED CALU TOURNAMENT ROUNDS — each round is ONE batched panel
    LU (blocked.panel_getrf_batched) instead of vmap(lax.linalg.lu)'s
    sequential per-block custom-call loop. Guarded by a dispatch-policy
    spy and an HLO probe (no lapack getrf custom-call in the lowered
    tournament).

(c) MESH PERM CORRUPTION, ROOT-CAUSED (the CHANGES.md round-6 open
    item): two pre-0.6 SPMD partitioner mis-lowerings — the
    concatenate in perm composition (blocked.lift_tail_perm is the
    fix) and the permutation gathers of a ROW-SHARDED panel operand
    (blocked.replicate_on_grid — the panel broadcast — is the fix).
    Regression tests pin both, at the minimal-repro level and through
    the full mesh getrf at the previously-failing (n=256, nb=64)
    shape. The lookahead restructure does NOT change the lowering
    class: both lookahead arms were corrupted identically before the
    fix and are correct identically after (asserted below).
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
from slate_tpu.ops import blocked

RNG = np.random.default_rng(71)

_SEQ = Options(lookahead=0)


def _randn(m, n, dtype):
    a = RNG.standard_normal((m, n))
    if np.issubdtype(np.dtype(dtype), np.complexfloating):
        a = a + 1j * RNG.standard_normal((m, n))
    return np.asarray(a, dtype)


# -- (a) bit-identity: lookahead=1 vs lookahead=0 ---------------------------

@pytest.mark.parametrize("dtype", [np.float32, np.float64, np.complex128])
def test_potrf_lookahead_bit_identical(dtype):
    """Pure op reordering: every slab gemm of the pipeline is the same
    op as in the sequential schedule, so the factors must agree BIT
    FOR BIT. (n = 4 panels: ≥ 2 pipelined steps with a non-empty
    remainder each — the smallest shape where every pipeline branch
    runs; tier-1 budget.)"""
    n, nb = 128, 32
    a = np.asarray(random_spd(n, dtype=dtype, seed=9))
    A = st.hermitian(np.tril(a), nb=nb, uplo=Uplo.Lower)
    L1, i1 = st.potrf(A)
    L0, i0 = st.potrf(A, _SEQ)
    assert int(i1) == int(i0) == 0
    np.testing.assert_array_equal(np.asarray(L1.data), np.asarray(L0.data))


@pytest.mark.parametrize("dtype", [np.float32, np.float64, np.complex128])
def test_getrf_lookahead_bit_identical(dtype):
    n, nb = 128, 32
    a = _randn(n, n, dtype)
    A = st.from_dense(a, nb=nb)
    LU1, p1, i1 = st.getrf(A)
    LU0, p0, i0 = st.getrf(A, _SEQ)
    assert int(i1) == int(i0)
    np.testing.assert_array_equal(np.asarray(p1), np.asarray(p0))
    np.testing.assert_array_equal(np.asarray(LU1.data), np.asarray(LU0.data))


@pytest.mark.parametrize("dtype", [np.float32, np.complex128])
def test_geqrf_lookahead_bit_identical(dtype):
    m, n, nb = 160, 128, 32  # kt = 4: the pipeline splits twice
    a = _randn(m, n, dtype)
    A = st.from_dense(a, nb=nb)
    q1 = st.geqrf(A)
    q0 = st.geqrf(A, _SEQ)
    np.testing.assert_array_equal(np.asarray(q1.vr), np.asarray(q0.vr))
    np.testing.assert_array_equal(np.asarray(q1.t), np.asarray(q0.t))


def test_lookahead_bit_identical_mesh(grid2x4):
    """The pipeline must survive GSPMD partitioning bit-for-bit too
    (same ops, same shardings — rebalance constraints are applied per
    slab in both schedules). One mesh driver pair (getrf — the richest
    composition: pivot-fused gathers + split gemms + deferred swaps)
    keeps this inside the tier-1 budget; potrf/geqrf mesh runs are
    covered by test_distribution's grid-vs-1×1 agreement."""
    n, nb = 128, 32
    a = _randn(n, n, np.float64)
    Ag = st.from_dense(a, nb=nb, grid=grid2x4)
    LU1, p1, _ = st.getrf(Ag)
    LU0, p0, _ = st.getrf(Ag, _SEQ)
    np.testing.assert_array_equal(np.asarray(p1), np.asarray(p0))
    np.testing.assert_array_equal(np.asarray(LU1.data), np.asarray(LU0.data))


# -- (a) structural dependence guard (jaxpr reachability) -------------------

def _ancestor_eqns(jaxpr, target_idx):
    """Indices of eqns reachable backwards from eqn ``target_idx``."""
    eqns = jaxpr.eqns
    producer = {}
    for i, e in enumerate(eqns):
        for v in e.outvars:
            producer[v] = i
    seen, stack = set(), [target_idx]
    while stack:
        i = stack.pop()
        if i in seen:
            continue
        seen.add(i)
        for v in eqns[i].invars:
            if getattr(v, "count", None) is None:
                continue  # Literal operands have no producer
            j = producer.get(v)
            if j is not None:
                stack.append(j)
    return seen


def _pjit_indices(jaxpr, name):
    out = []
    for i, e in enumerate(jaxpr.eqns):
        # the nested-jit call primitive: "jit" in JAX 0.9.0, "pjit" before
        if e.primitive.name in ("jit", "pjit", "closed_call", "core_call"):
            if e.params.get("name") == name:
                out.append(i)
    return out


def _max_ancestor_dot_width(jaxpr, target_idx):
    """Widest 2-D dot_general output among the target eqn's ancestors
    (0 if none) — the probe's measure of which trailing slabs the
    panel factor depends on."""
    widths = [0]
    for i in _ancestor_eqns(jaxpr, target_idx):
        e = jaxpr.eqns[i]
        if e.primitive.name == "dot_general":
            shp = e.outvars[0].aval.shape
            if len(shp) == 2:
                widths.append(shp[1])
    return max(widths)


def _ancestor_remainder_dots(jaxpr, target_idx, s, nb):
    """Count 2-D ancestor dots that are REMAINDER slabs of step 0 —
    potrf's trailing slabs are all nb wide, so the discriminator is
    the shrinking ROW count: the next-panel slab has s−nb rows, the
    remainder slabs s−2nb, s−3nb, …"""
    count = 0
    for i in _ancestor_eqns(jaxpr, target_idx):
        e = jaxpr.eqns[i]
        if e.primitive.name == "dot_general":
            shp = e.outvars[0].aval.shape
            if len(shp) == 2 and shp[1] == nb and shp[0] <= s - 2 * nb:
                count += 1
    return count


def test_jaxpr_potrf_panel_decoupled_from_remainder():
    """THE structural lookahead assertion: the step-1 tile factor of
    the pipeline depends on the next-panel slab ONLY — no remainder
    slab dot (rows ≤ s−2nb) among its ancestors; in the sequential
    schedule the remainder slabs ARE ancestors (the probe's positive
    control)."""
    nb = 32
    s = 4 * nb
    a = jnp.eye(s, dtype=jnp.float32) * s

    def tile_indices(lookahead):
        jaxpr = jax.make_jaxpr(
            lambda x: chol_mod._potrf_iter(x, nb, "high", lookahead))(
                a).jaxpr
        idx = _pjit_indices(jaxpr, "_tile_chol")
        assert len(idx) >= 2, "probe lost the tile-factor call sites"
        return jaxpr, idx

    jx1, idx1 = tile_indices(1)
    assert _ancestor_remainder_dots(jx1, idx1[1], s, nb) == 0, (
        "lookahead tile factor depends on a remainder slab")
    jx0, idx0 = tile_indices(0)
    assert _ancestor_remainder_dots(jx0, idx0[1], s, nb) > 0, (
        "positive control: sequential tile factor should depend on the "
        "remainder slabs")


def test_jaxpr_getrf_panel_decoupled_from_remainder():
    """Same decoupling for LU: the step-1 panel factorization's
    ancestor dots are at most nb wide under lookahead=1; the
    sequential schedule shows the (w−nb)-wide full trailing dot."""
    nb = 32
    w = 4 * nb
    a = jnp.asarray(RNG.standard_normal((w, w)).astype(np.float32))

    def panel_indices(lookahead):
        jaxpr = jax.make_jaxpr(
            lambda x: lu_mod._getrf_iter(x, nb, "high",
                                         lookahead=lookahead))(a).jaxpr
        idx = _pjit_indices(jaxpr, "panel_getrf_jit")
        assert len(idx) >= 2
        return jaxpr, idx

    jx1, idx1 = panel_indices(1)
    assert _max_ancestor_dot_width(jx1, idx1[1]) <= nb
    jx0, idx0 = panel_indices(0)
    assert _max_ancestor_dot_width(jx0, idx0[1]) > nb


# -- (a) scheduled-HLO interleaving (needs a reordering scheduler) ----------

def _scheduled_positions(n=256, nb=32):
    """Compiled (scheduled) potrf entry at lookahead=1, mapping each
    line to the named scopes the ops carry (jax.named_scope metadata
    survives into compiled-HLO op_name)."""
    spd = np.asarray(random_spd(n, dtype=jnp.float32, seed=5))
    A = st.hermitian(np.tril(spd), nb=nb, uplo=Uplo.Lower)

    def f(A):
        return st.potrf(A)[0].data

    hlo = jax.jit(f).lower(A).compile().as_text()
    m = re.search(r"^ENTRY [^{]*\{(.*?)^\}", hlo, re.S | re.M)
    assert m, "no ENTRY computation"
    return hlo, m.group(1).splitlines()


def test_scheduled_hlo_lookahead_panel_interleaved():
    """The schedule-level assertion (test_distribution P3 technique):
    some panel-(k+1) lookahead op must be SCHEDULED before the last
    remainder op of step k. XLA:CPU's sequential scheduler keeps data
    order, so (like the async-collective test) this skips when the
    property doesn't hold on a CPU backend; it is the standing check
    for a TPU-attached session."""
    hlo, lines = _scheduled_positions()
    nt = 256 // 32
    interleaved = 0
    for k in range(nt - 1):
        first_panel = last_rest = None
        for i, ln in enumerate(lines):
            if f"potrf_l{k + 1}_tile_lookahead" in ln and first_panel is None:
                first_panel = i
            if f"potrf_l{k}_trail_rest" in ln:
                last_rest = i
        if first_panel is not None and last_rest is not None \
                and first_panel < last_rest:
            interleaved += 1
    if interleaved == 0:
        if jax.default_backend() != "tpu":
            pytest.skip("backend scheduler keeps trace order (no "
                        "panel/remainder interleaving in scheduled "
                        "HLO); the assertion needs a TPU backend")
        assert interleaved > 0, (
            "TPU schedule never hoisted a lookahead panel before the "
            "previous step's remainder")
    # whichever backend: the lookahead scopes must exist in the
    # compiled module at all (the pipeline actually traced)
    assert "tile_lookahead" in hlo


def test_lookahead_scopes_absent_in_sequential_program():
    """lookahead=0 must reproduce the round-6 program: no lookahead
    scope appears anywhere in its compiled module."""
    n, nb = 128, 32
    spd = np.asarray(random_spd(n, dtype=jnp.float32, seed=6))
    A = st.hermitian(np.tril(spd), nb=nb, uplo=Uplo.Lower)

    def f(A):
        return st.potrf(A, _SEQ)[0].data

    hlo = jax.jit(f).lower(A).compile().as_text()
    assert "tile_lookahead" not in hlo


def test_herk_trailing_inplace_split_equals_whole():
    """The j_start/j_stop slab-range split the pipeline relies on:
    next-slab call + remainder call == one whole-range call, bitwise
    (identical slab gemms, only call boundaries differ)."""
    s, k1, nb = 160, 32, 32
    a = jnp.asarray(RNG.standard_normal((s, s)))
    pan = jnp.asarray(RNG.standard_normal((s - k1, nb)))
    whole = blocked.herk_trailing_inplace(a, pan, k1, nb)
    split = blocked.herk_trailing_inplace(a, pan, k1, nb,
                                          j_stop=k1 + nb)
    split = blocked.herk_trailing_inplace(split, pan, k1, nb,
                                          j_start=k1 + nb)
    np.testing.assert_array_equal(np.asarray(whole), np.asarray(split))


# -- (b) batched CALU tournament rounds -------------------------------------

def test_calu_batched_dispatch_policy(monkeypatch):
    """CALU routes every tournament round through the batched panel
    LU."""
    calls = {"batched": 0}
    orig = blocked.panel_getrf_batched

    def spy(stack, _o=orig):
        calls["batched"] += 1
        return _o(stack)

    monkeypatch.setattr(blocked, "panel_getrf_batched", spy)
    n, nb = 96, 32
    a = _randn(n, n, np.float64)
    A = st.from_dense(a, nb=nb)
    st.getrf(A, Options(method_lu=MethodLU.CALU))
    assert calls["batched"] > 0, "batched rounds never consulted"


def test_hlo_calu_rounds_have_no_lu_custom_call():
    """The lowered CALU program contains NO lax.linalg.lu custom-call
    (the per-block sequential loop)."""
    n, nb = 128, 32
    a = _randn(n, n, np.float32)
    A = st.from_dense(a, nb=nb)

    def lower_text(opts):
        def f(A):
            return st.getrf(A, opts)[0].data
        return jax.jit(f).lower(A).as_text()

    assert "getrf_ffi" not in lower_text(Options(method_lu=MethodLU.CALU))


def test_panel_getrf_batched_matches_sequential():
    """The batched round kernel == per-chunk fori base, chunk by
    chunk (it IS vmap of the same base)."""
    stack = jnp.asarray(RNG.standard_normal((3, 64, 16)))
    lus, perms, infos = blocked.panel_getrf_batched(stack)
    for b in range(3):
        lu_r, p_r, i_r = blocked._panel_getrf_base(stack[b])
        np.testing.assert_array_equal(np.asarray(perms[b]), np.asarray(p_r))
        np.testing.assert_allclose(np.asarray(lus[b]), np.asarray(lu_r),
                                   rtol=1e-13, atol=1e-13)
        assert int(infos[b]) == int(i_r)


# -- (c) mesh perm corruption: root cause pinned ----------------------------

def test_compose_tail_sharded(grid2x4):
    """Minimal repro of the round-6 open item, now a regression guard:
    composing perms with a SHARDED tail must stay a valid permutation.
    (The old concatenate formulation produced out-of-range indices
    under the pre-0.6 partitioner — lift_tail_perm's docstring.)"""
    from jax.sharding import NamedSharding, PartitionSpec as P
    from slate_tpu.core.grid import ROW_AXIS

    p1 = jnp.asarray(RNG.permutation(256).astype(np.int32))
    p2 = jnp.asarray(RNG.permutation(224).astype(np.int32))
    ref = np.asarray(blocked._compose_tail(p1, p2, 32))
    sh = NamedSharding(grid2x4.mesh, P(ROW_AXIS))
    out = np.asarray(jax.jit(blocked._compose_tail, static_argnums=2)(
        jax.device_put(p1, sh), jax.device_put(p2, sh), 32))
    assert sorted(out.tolist()) == list(range(256))
    np.testing.assert_array_equal(out, ref)


@pytest.mark.slow
def test_mesh_getrf_nb64_perm_regression(grid2x4):
    """The full previously-failing shape (n=256, nb=64): the perm must
    be a valid permutation, match the 1×1 grid, and factor correctly —
    under BOTH lookahead arms (the restructure does not change the
    lowering class: the corruption lived in perm composition and the
    sharded-panel gathers, fixed by lift_tail_perm +
    replicate_on_grid). Slow (round-20 tier-1 budget: two n=256 mesh
    factor compiles). Tier-1 siblings: test_compose_tail_sharded pins
    the root-cause perm-composition contract on the same grid, and
    test_distribution.py's grid_matches_single_device[getrf] pins
    mesh-getrf correctness."""
    n, nb = 256, 64
    a = _randn(n, n, np.float64)
    Ag = st.from_dense(a, nb=nb, grid=grid2x4)
    p_ref = np.asarray(st.getrf(st.from_dense(a, nb=nb))[1])
    for opts in (Options(), _SEQ):
        LU, perm, info = st.getrf(Ag, opts)
        perm = np.asarray(perm)
        assert sorted(perm.tolist()) == list(range(n)), \
            "mesh perm is not a permutation (round-6 corruption back?)"
        np.testing.assert_array_equal(perm, p_ref)
        lu = LU.to_numpy()
        L = np.tril(lu, -1) + np.eye(n)
        U = np.triu(lu)
        resid = np.abs(a[perm] - L @ U).max() / (
            np.linalg.norm(a, 1) * n * np.finfo(np.float64).eps)
        assert resid < 30.0


def test_mesh_calu_nb64(grid2x4):
    """CALU on the mesh at the formerly-failing block size (its
    tournament perms ride the same composition machinery)."""
    n, nb = 128, 64
    a = _randn(n, n, np.float64)
    LU, perm, info = st.getrf(st.from_dense(a, nb=nb, grid=grid2x4),
                              Options(method_lu=MethodLU.CALU))
    perm = np.asarray(perm)
    assert sorted(perm.tolist()) == list(range(n))
    lu = LU.to_numpy()
    L = np.tril(lu, -1) + np.eye(n)
    U = np.triu(lu)
    resid = np.abs(a[perm] - L @ U).max() / (
        np.linalg.norm(a, 1) * n * np.finfo(np.float64).eps)
    assert resid < 30.0
