"""Pallas TPU kernels (ops/pallas_ops): the tile Cholesky and the LU
and QR panel bases, exercised in Pallas interpreter mode (runs on the
CPU mesh), the same code path Mosaic compiles on a real TPU; their
eligibility gates and the jnp herk recursion that the factorizations'
trailing updates use are covered alongside.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from slate_tpu.ops import blocked, pallas_ops

RNG = np.random.default_rng(31)


def _ref_lower(c, a):
    return c - a @ a.T


def test_herk_lower_rec_unchanged_by_routing():
    # the blocked recursion computes the lower triangle of C − A·Aᵀ
    n, k = 320, 128
    c = RNG.standard_normal((n, n)).astype(np.float32)
    a = RNG.standard_normal((n, k)).astype(np.float32)
    rec = np.asarray(blocked.herk_lower_rec(jnp.asarray(c), jnp.asarray(a),
                                            base=128))
    ref = _ref_lower(c.astype(np.float64), a.astype(np.float64))
    np.testing.assert_allclose(np.tril(rec), np.tril(ref), atol=1e-4)


def _chol_tile_interpret_case(b, junk_upper):
    x = RNG.standard_normal((b, b)).astype(np.float32)
    a = (x @ x.T + b * np.eye(b)).astype(np.float32)
    if junk_upper:
        a = np.tril(a) + 1e6 * np.triu(
            RNG.standard_normal((b, b)).astype(np.float32), 1)
    lk = np.asarray(pallas_ops.chol_tile(jnp.asarray(a), interpret=True))
    lref = np.linalg.cholesky(
        np.tril(a).astype(np.float64)
        + np.tril(a, -1).astype(np.float64).T)
    assert np.abs(lk - lref).max() / np.abs(lref).max() < 1e-5
    assert np.allclose(np.triu(lk, 1), 0.0)


@pytest.mark.slow  # ~14 s interpret-mode numerics (round-10 headroom);
# the dispatch-wiring spy tests keep the Pallas seam in tier-1
def test_chol_tile_kernel_interpret():
    """In-VMEM blocked Cholesky kernel (round 5): interpret-mode
    correctness vs LAPACK-precision numpy, including the strict-upper
    zeroing contract. b=128 exercises a single 128-panel with all four
    32-micro steps (the b=256 cross-panel case runs under -m slow —
    interpret-mode dispatch makes it ~30 s of the tier-1 budget)."""
    _chol_tile_interpret_case(128, junk_upper=False)


@pytest.mark.slow
def test_chol_tile_kernel_interpret_cross_panel():
    """b=256 adds the cross-panel left/top trailing update (the
    `if jb:` branch), with junk in the strict upper triangle to pin
    the lower-only read contract."""
    _chol_tile_interpret_case(256, junk_upper=True)


@pytest.mark.slow  # ~19 s interpret-mode dispatch (round-22 headroom);
# tier-1 sibling: test_chol_tile_nan_poisons_nonspd_single_micro
def test_chol_tile_nan_poisons_nonspd():
    """Non-SPD input must NaN-poison (the _tile_chol info contract) —
    b=128 breaks in a LATER micro step, so the poison must propagate
    through the trailing updates."""
    b = 128
    x = RNG.standard_normal((b, b)).astype(np.float32)
    a = (x @ x.T + b * np.eye(b)).astype(np.float32)
    a[40, 40] = -a[40, 40] - abs(a).sum()
    lk = np.asarray(pallas_ops.chol_tile(jnp.asarray(a), interpret=True))
    assert np.isnan(lk[40:, 40:]).any()


def test_chol_tile_nan_poisons_nonspd_single_micro():
    """Tier-1 sibling of the b=128 case above: the same poison contract
    at its source — the 32-micro factorization (_chol_cols_unrolled),
    where rsqrt of the negative pivot first goes NaN. (chol_tile itself
    requires b >= _CHOL_IB=128, which is interpret-mode-slow; the kernel
    builds its panels out of exactly this micro step.)"""
    m = 32
    x = RNG.standard_normal((m, m)).astype(np.float32)
    a = (x @ x.T + m * np.eye(m)).astype(np.float32)
    a[10, 10] = -a[10, 10] - abs(a).sum()
    lk = np.asarray(pallas_ops._chol_cols_unrolled(jnp.asarray(a), m))
    assert np.isnan(lk[10:, 10:]).any()
    # healthy columns before the bad pivot stay finite
    assert np.isfinite(lk[:, :10]).all()


def test_chol_eligibility_gates():
    f32 = jnp.float32.dtype
    # shape/dtype gates are backend-independent
    assert not pallas_ops.chol_eligible(100, f32)
    assert not pallas_ops.chol_eligible(2048, f32)
    assert not pallas_ops.chol_eligible(512, jnp.float64)
    assert not pallas_ops.chol_eligible(512, jnp.complex64)


def test_lu_panel_kernel_interpret():
    """In-VMEM pivoted LU panel base (round 5): interpret-mode parity
    with the fori base — identical LU content, identical gather perm,
    identical info, including the zero-column keep-diagonal case."""
    for (h, w) in ((128, 32), (256, 16)):
        a = RNG.standard_normal((h, w)).astype(np.float32)
        lu_k, p_k, i_k = pallas_ops.lu_panel_base(
            jnp.asarray(a), interpret=True)
        lu_r, p_r, i_r = blocked._panel_getrf_base(jnp.asarray(a))
        assert int(i_k) == int(i_r) == 0
        np.testing.assert_array_equal(np.asarray(p_k), np.asarray(p_r))
        np.testing.assert_allclose(np.asarray(lu_k), np.asarray(lu_r),
                                   atol=1e-5)
        lm = np.tril(np.asarray(lu_k), -1)[:, :w]
        lm[np.arange(w), np.arange(w)] = 1.0
        u = np.triu(np.asarray(lu_k))[:w, :]
        np.testing.assert_allclose(a[np.asarray(p_k)], lm @ u, atol=1e-4)
    a = RNG.standard_normal((64, 8)).astype(np.float32)
    a[:, 3] = 0.0
    _, _, i_k = pallas_ops.lu_panel_base(jnp.asarray(a), interpret=True)
    assert int(i_k) == 4


def test_lu_panel_eligibility_gates():
    f32 = jnp.float32.dtype
    assert not pallas_ops.lu_panel_eligible(1024, 4, f32)       # w too small
    assert not pallas_ops.lu_panel_eligible(16, 32, f32)        # h < w
    assert not pallas_ops.lu_panel_eligible(10 ** 6, 32, f32)   # VMEM
    assert not pallas_ops.lu_panel_eligible(1024, 32, jnp.float64)


def test_qr_panel_kernel_interpret():
    """In-VMEM Householder QR panel base (round 5): interpret-mode
    parity with the fori base — identical packed V\\R and taus,
    including the degenerate zero-tail column (tau = 0, H = I)."""
    for (h, w) in ((128, 32), (256, 16)):
        a = RNG.standard_normal((h, w)).astype(np.float32)
        vr_k, tau_k = pallas_ops.qr_panel_base(jnp.asarray(a),
                                               interpret=True)
        vr_r, tau_r = blocked._panel_geqrf_base(jnp.asarray(a))
        np.testing.assert_allclose(np.asarray(tau_k), np.asarray(tau_r),
                                   atol=1e-6)
        np.testing.assert_allclose(np.asarray(vr_k), np.asarray(vr_r),
                                   atol=1e-4)
        # reconstruction: Q·R == A
        v = np.tril(np.asarray(vr_k), -1)[:, :w]
        v[np.arange(w), np.arange(w)] = 1.0
        r = np.triu(np.asarray(vr_k))[:w, :]
        q = np.eye(h, dtype=np.float64)
        for j in range(w - 1, -1, -1):
            vj = v[:, j].astype(np.float64)
            q = q - float(tau_k[j]) * np.outer(vj, vj @ q)
        np.testing.assert_allclose(q[:, :w] @ r.astype(np.float64), a,
                                   atol=5e-4)
    a = RNG.standard_normal((64, 8)).astype(np.float32)
    a[:, 3] = 0.0  # whole column zero -> tau[3] == 0 after updates
    vr_k, tau_k = pallas_ops.qr_panel_base(jnp.asarray(a), interpret=True)
    vr_r, tau_r = blocked._panel_geqrf_base(jnp.asarray(a))
    np.testing.assert_allclose(np.asarray(tau_k), np.asarray(tau_r),
                               atol=1e-6)
    np.testing.assert_allclose(np.asarray(vr_k), np.asarray(vr_r),
                               atol=1e-4)


def test_qr_panel_eligibility_gates():
    f32 = jnp.float32.dtype
    assert not pallas_ops.qr_panel_eligible(1024, 4, f32)       # w too small
    assert not pallas_ops.qr_panel_eligible(16, 32, f32)        # h < w
    assert not pallas_ops.qr_panel_eligible(10 ** 6, 32, f32)   # VMEM
    assert not pallas_ops.qr_panel_eligible(1024, 32, jnp.float64)


# -- round 7: deeper-unrolled WIDE panel bases ------------------------------

@pytest.mark.slow  # ~6 s interpret-mode numerics (round-10 headroom)
def test_qr_panel_wide_kernel_interpret():
    """Micro-blocked wide QR panel kernel (round 7): interpret-mode
    correctness at 64/128-wide bases — f32-level agreement with the
    fori base (the compact-WY deferral reassociates, so tolerance, not
    bit parity) and a float64 Q·R reconstruction, plus the degenerate
    zero-column contract (tau = 0)."""
    for (h, w) in ((128, 64), (192, 64), (256, 128)):
        a = RNG.standard_normal((h, w)).astype(np.float32)
        vr_k, tau_k = pallas_ops.qr_panel_base_wide(jnp.asarray(a),
                                                    interpret=True)
        vr_r, tau_r = blocked._panel_geqrf_base(jnp.asarray(a))
        vr_k, tau_k = np.asarray(vr_k), np.asarray(tau_k)
        np.testing.assert_allclose(tau_k, np.asarray(tau_r), atol=2e-6)
        np.testing.assert_allclose(vr_k, np.asarray(vr_r), atol=2e-4)
        v = np.tril(vr_k, -1)[:, :w]
        v[np.arange(w), np.arange(w)] = 1.0
        r = np.triu(vr_k)[:w, :]
        q = np.eye(h, dtype=np.float64)
        for j in range(w - 1, -1, -1):
            vj = v[:, j].astype(np.float64)
            q = q - float(tau_k[j]) * np.outer(vj, vj @ q)
        np.testing.assert_allclose(q[:, :w] @ r.astype(np.float64), a,
                                   atol=1e-3)
    a = RNG.standard_normal((128, 64)).astype(np.float32)
    a[:, 37] = 0.0
    _, tau_k = pallas_ops.qr_panel_base_wide(jnp.asarray(a),
                                             interpret=True)
    assert float(tau_k[37]) == 0.0


def test_lu_panel_kernel_wide_interpret():
    """The LU base kernel at WIDE widths (round-7 dispatch widening):
    its column loop is arithmetic-identical to the fori base at any
    width, so a 64/128-wide invocation must match bit-for-bit."""
    for (h, w) in ((128, 64), (256, 128)):
        a = RNG.standard_normal((h, w)).astype(np.float32)
        lu_k, p_k, i_k = pallas_ops.lu_panel_base(
            jnp.asarray(a), interpret=True)
        lu_r, p_r, i_r = blocked._panel_getrf_base(jnp.asarray(a))
        assert int(i_k) == int(i_r)
        np.testing.assert_array_equal(np.asarray(p_k), np.asarray(p_r))
        np.testing.assert_array_equal(np.asarray(lu_k), np.asarray(lu_r))


def test_wide_panel_dispatch_policy(monkeypatch):
    """With a TPU backend reported, panel_getrf/panel_geqrf route a
    wide (64/128-wide, short) base to ONE kernel invocation instead of
    recursing into 32-wide bases; tall panels stay on the recursion
    (cells gate)."""
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    f32 = jnp.float32.dtype
    assert pallas_ops.qr_panel_wide_eligible(2048, 128, f32)
    assert pallas_ops.qr_panel_wide_eligible(4096, 64, f32)
    assert not pallas_ops.qr_panel_wide_eligible(4096, 128, f32)  # cells
    assert not pallas_ops.qr_panel_wide_eligible(2048, 32, f32)   # base kern
    assert not pallas_ops.qr_panel_wide_eligible(2048, 80, f32)   # MB align
    assert pallas_ops.lu_panel_eligible(2048, 128, f32)

    calls = {"qr_wide": 0, "lu_wide": 0}

    def fake_qr_wide(a, **kw):
        calls["qr_wide"] += 1
        return blocked._panel_geqrf_base(a)

    def fake_lu_wide(a, **kw):
        calls["lu_wide"] += 1
        return blocked._panel_getrf_base(a)

    monkeypatch.setattr(pallas_ops, "qr_panel_base_wide", fake_qr_wide)
    monkeypatch.setattr(pallas_ops, "lu_panel_base", fake_lu_wide)
    a = jnp.asarray(RNG.standard_normal((256, 128)).astype(np.float32))
    blocked.panel_geqrf(a)
    assert calls["qr_wide"] == 1, "wide QR base did not own the panel"
    blocked.panel_getrf(a)
    assert calls["lu_wide"] == 1, "wide LU base did not own the panel"
