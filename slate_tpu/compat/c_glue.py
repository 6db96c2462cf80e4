"""Glue between the embedded-interpreter C API (native/capi.c +
generated native/capi_gen.c) and the Python drivers: unpack C
memoryviews (column-major, LAPACK layout), call the compat lapack_api,
copy results back into the caller's buffers, and return info.

Every entry point is dtype-generic: the first argument ``dt`` is the
LAPACK precision letter (s/d/c/z) baked into the generated C symbol
(slate_tpu_sgesv passes "s", ...). Reference analog:
src/c_api/wrappers.cc — the hand-written core that the generated C API
(tools/c_api/generate_wrappers.py) dispatches into; our generator is
tools/gen_capi.py.
"""

from __future__ import annotations

import numpy as np

_DT = {"s": np.float32, "d": np.float64,
       "c": np.complex64, "z": np.complex128}
_RDT = {"s": np.float32, "d": np.float64,
        "c": np.float32, "z": np.float64}


def _as_cm(buf, rows, ld, cols, dtype):
    """View a C memoryview as a column-major (rows, cols) array slice."""
    flat = np.frombuffer(buf, dtype=dtype)
    full = flat[: ld * cols].reshape((cols, ld)).T  # (ld, cols) col-major
    return full[:rows, :]


def _lp():
    from . import lapack_api
    return lapack_api


def c_gesv(dt, n, nrhs, a_buf, lda, ipiv_buf, b_buf, ldb) -> int:
    et = _DT[dt]
    a = _as_cm(a_buf, n, lda, n, et)
    b = _as_cm(b_buf, n, ldb, nrhs, et)
    lu, ipiv, x, info = getattr(_lp(), dt + "gesv")(
        n, nrhs, np.array(a), n, b, n)
    a[:, :] = lu
    b[:, :] = x
    np.frombuffer(ipiv_buf, dtype=np.int64)[:n] = ipiv
    return int(info)


def c_potrf(dt, uplo, n, a_buf, lda) -> int:
    et = _DT[dt]
    a = _as_cm(a_buf, n, lda, n, et)
    f, info = getattr(_lp(), dt + "potrf")(uplo, n, np.array(a), n)
    if uplo.lower().startswith("l"):
        a[:, :] = np.tril(f) + np.triu(np.array(a), 1)
    else:
        a[:, :] = np.triu(f) + np.tril(np.array(a), -1)
    return int(info)


def c_posv(dt, uplo, n, nrhs, a_buf, lda, b_buf, ldb) -> int:
    et = _DT[dt]
    a = _as_cm(a_buf, n, lda, n, et)
    b = _as_cm(b_buf, n, ldb, nrhs, et)
    x, info = getattr(_lp(), dt + "posv")(
        uplo, n, nrhs, np.array(a), n, np.array(b), n)
    b[:, :] = x
    return int(info)


def c_gels(dt, m, n, nrhs, a_buf, lda, b_buf, ldb) -> int:
    et = _DT[dt]
    a = _as_cm(a_buf, m, lda, n, et)
    b = _as_cm(b_buf, max(m, n), ldb, nrhs, et)
    x, info = getattr(_lp(), dt + "gels")(
        "n", m, n, nrhs, np.array(a), m, np.array(b[:m]), m)
    if info != 0:  # driver failure: report info, leave b untouched
        return int(info)
    b[:n, :] = x
    return int(info)


def c_getrf(dt, m, n, a_buf, lda, ipiv_buf) -> int:
    et = _DT[dt]
    a = _as_cm(a_buf, m, lda, n, et)
    lu, ipiv, info = getattr(_lp(), dt + "getrf")(m, n, np.array(a), m)
    a[:, :] = lu
    k = min(m, n)
    np.frombuffer(ipiv_buf, dtype=np.int64)[:k] = ipiv[:k]
    return int(info)


def c_getrs(dt, trans, n, nrhs, a_buf, lda, ipiv_buf, b_buf, ldb) -> int:
    et = _DT[dt]
    a = _as_cm(a_buf, n, lda, n, et)
    b = _as_cm(b_buf, n, ldb, nrhs, et)
    ipiv = np.array(np.frombuffer(ipiv_buf, dtype=np.int64)[:n])
    x, info = getattr(_lp(), dt + "getrs")(
        trans, n, nrhs, np.array(a), n, ipiv, np.array(b), n)
    b[:, :] = x
    return int(info)


def c_getri(dt, n, a_buf, lda, ipiv_buf) -> int:
    et = _DT[dt]
    a = _as_cm(a_buf, n, lda, n, et)
    ipiv = np.array(np.frombuffer(ipiv_buf, dtype=np.int64)[:n])
    inv, info = getattr(_lp(), dt + "getri")(n, np.array(a), n, ipiv)
    a[:, :] = inv
    return int(info)


def c_potrs(dt, uplo, n, nrhs, a_buf, lda, b_buf, ldb) -> int:
    et = _DT[dt]
    a = _as_cm(a_buf, n, lda, n, et)
    b = _as_cm(b_buf, n, ldb, nrhs, et)
    x, info = getattr(_lp(), dt + "potrs")(
        uplo, n, nrhs, np.array(a), n, np.array(b), n)
    b[:, :] = x
    return int(info)


def c_heev(dt, jobz, uplo, n, a_buf, lda, w_buf) -> int:
    et = _DT[dt]
    name = dt + ("syev" if dt in "sd" else "heev")
    a = _as_cm(a_buf, n, lda, n, et)
    w, z, info = getattr(_lp(), name)(jobz, uplo, n, np.array(a), n)
    np.frombuffer(w_buf, dtype=_RDT[dt])[:n] = np.asarray(w)
    if z is not None:
        a[:, :] = z  # LAPACK: eigenvectors overwrite A when jobz='V'
    return int(info)


def c_gesvd(dt, jobu, jobvt, m, n, a_buf, lda, s_buf, u_buf, ldu, vt_buf,
            ldvt) -> int:
    # thin ('S') and values-only ('N') jobs only: 'A' (full square U/VT)
    # and 'O' (overwrite A) would leave part of the caller's buffers
    # uninitialized — reject loudly instead of returning a partial
    # result with rc=0 (the pre-generator C wrapper did the same)
    if jobu and jobu[:1].lower() in ("a", "o"):
        return -1
    if jobvt and jobvt[:1].lower() in ("a", "o"):
        return -2
    et = _DT[dt]
    a = _as_cm(a_buf, m, lda, n, et)
    s, u, vt, info = getattr(_lp(), dt + "gesvd")(
        jobu, jobvt, m, n, np.array(a), m)
    if info:
        return int(info)
    k = min(m, n)
    np.frombuffer(s_buf, dtype=_RDT[dt])[:k] = np.asarray(s)[:k]
    if u is not None and u_buf is not None:
        _as_cm(u_buf, m, ldu, k, et)[:, :] = np.asarray(u)[:m, :k]
    if vt is not None and vt_buf is not None:
        _as_cm(vt_buf, k, ldvt, n, et)[:, :] = np.asarray(vt)[:k, :n]
    return 0


def c_gemm(dt, transa, transb, m, n, k, alpha, a_buf, lda, b_buf, ldb,
           beta, c_buf, ldc) -> int:
    et = _DT[dt]
    rows_a = m if transa.lower().startswith("n") else k
    cols_a = k if transa.lower().startswith("n") else m
    rows_b = k if transb.lower().startswith("n") else n
    cols_b = n if transb.lower().startswith("n") else k
    a = _as_cm(a_buf, rows_a, lda, cols_a, et)
    b = _as_cm(b_buf, rows_b, ldb, cols_b, et)
    c = _as_cm(c_buf, m, ldc, n, et)
    out = getattr(_lp(), dt + "gemm")(
        transa, transb, m, n, k, alpha, np.array(a), rows_a,
        np.array(b), rows_b, beta, np.array(c), m)
    c[:, :] = out
    return 0


def c_trsm(dt, side, uplo, transa, diag, m, n, alpha, a_buf, lda, b_buf,
           ldb) -> int:
    et = _DT[dt]
    ka = m if side.lower().startswith("l") else n
    a = _as_cm(a_buf, ka, lda, ka, et)
    b = _as_cm(b_buf, m, ldb, n, et)
    out = getattr(_lp(), dt + "trsm")(
        side, uplo, transa, diag, m, n, alpha, np.array(a), ka,
        np.array(b), m)
    b[:, :] = out
    return 0


def c_trmm(dt, side, uplo, transa, diag, m, n, alpha, a_buf, lda, b_buf,
           ldb) -> int:
    et = _DT[dt]
    ka = m if side.lower().startswith("l") else n
    a = _as_cm(a_buf, ka, lda, ka, et)
    b = _as_cm(b_buf, m, ldb, n, et)
    out = getattr(_lp(), dt + "trmm")(
        side, uplo, transa, diag, m, n, alpha, np.array(a), ka,
        np.array(b), m)
    b[:, :] = out
    return 0


def c_lange(dt, norm, m, n, a_buf, lda, out_buf) -> int:
    et = _DT[dt]
    a = _as_cm(a_buf, m, lda, n, et)
    np.frombuffer(out_buf, dtype=np.float64)[0] = float(
        getattr(_lp(), dt + "lange")(norm, m, n, np.array(a), m))
    return 0


def c_potri(dt, uplo, n, a_buf, lda) -> int:
    et = _DT[dt]
    a = _as_cm(a_buf, n, lda, n, et)
    inv, info = getattr(_lp(), dt + "potri")(uplo, n, np.array(a), n)
    if info == 0:
        # LAPACK ?potri touches only the uplo triangle; preserve the
        # caller's data in the other one (same contract as c_potrf)
        if uplo.lower().startswith("l"):
            a[:, :] = np.tril(inv) + np.triu(np.array(a), 1)
        else:
            a[:, :] = np.triu(inv) + np.tril(np.array(a), -1)
    return int(info)


def c_geqrf(dt, m, n, a_buf, lda, tau_buf) -> int:
    et = _DT[dt]
    a = _as_cm(a_buf, m, lda, n, et)
    out, tau, info = getattr(_lp(), dt + "geqrf")(m, n, np.array(a), m)
    if info == 0:
        a[:, :] = out
        np.frombuffer(tau_buf, dtype=et)[: min(m, n)] = tau
    return int(info)


def c_gelqf(dt, m, n, a_buf, lda, tau_buf) -> int:
    et = _DT[dt]
    a = _as_cm(a_buf, m, lda, n, et)
    out, tau, info = getattr(_lp(), dt + "gelqf")(m, n, np.array(a), m)
    if info == 0:
        a[:, :] = out
        np.frombuffer(tau_buf, dtype=et)[: min(m, n)] = tau
    return int(info)


def c_unmqr(dt, side, trans, m, n, k, a_buf, lda, tau_buf, c_buf,
            ldc) -> int:
    et = _DT[dt]
    ra = m if side.lower().startswith("l") else n
    a = _as_cm(a_buf, ra, lda, k, et)
    tau = np.array(np.frombuffer(tau_buf, dtype=et)[:k])
    c = _as_cm(c_buf, m, ldc, n, et)
    name = dt + ("ormqr" if dt in "sd" else "unmqr")
    out, info = getattr(_lp(), name)(
        side, trans, m, n, k, np.array(a), ra, tau, np.array(c), m)
    if info == 0:
        c[:, :] = out
    return int(info)


def c_unmlq(dt, side, trans, m, n, k, a_buf, lda, tau_buf, c_buf,
            ldc) -> int:
    et = _DT[dt]
    ca = m if side.lower().startswith("l") else n  # LAPACK unmlq dims
    a = _as_cm(a_buf, k, lda, ca, et)
    tau = np.array(np.frombuffer(tau_buf, dtype=et)[:k])
    c = _as_cm(c_buf, m, ldc, n, et)
    name = dt + ("ormlq" if dt in "sd" else "unmlq")
    out, info = getattr(_lp(), name)(
        side, trans, m, n, k, np.array(a), k, tau, np.array(c), m)
    if info == 0:
        c[:, :] = out
    return int(info)


def c_heevd(dt, jobz, uplo, n, a_buf, lda, w_buf) -> int:
    et = _DT[dt]
    name = dt + ("syevd" if dt in "sd" else "heevd")
    a = _as_cm(a_buf, n, lda, n, et)
    w, z, info = getattr(_lp(), name)(jobz, uplo, n, np.array(a), n)
    np.frombuffer(w_buf, dtype=_RDT[dt])[:n] = np.asarray(w)
    if z is not None:
        a[:, :] = z
    return int(info)


def c_symm(dt, side, uplo, m, n, alpha, a_buf, lda, b_buf, ldb, beta,
           c_buf, ldc) -> int:
    et = _DT[dt]
    ka = m if side.lower().startswith("l") else n
    a = _as_cm(a_buf, ka, lda, ka, et)
    b = _as_cm(b_buf, m, ldb, n, et)
    c = _as_cm(c_buf, m, ldc, n, et)
    out = getattr(_lp(), dt + "symm")(
        side, uplo, m, n, alpha, np.array(a), ka, np.array(b), m,
        beta, np.array(c), m)
    c[:, :] = out
    return 0


def c_hemm(dt, side, uplo, m, n, alpha, a_buf, lda, b_buf, ldb, beta,
           c_buf, ldc) -> int:
    et = _DT[dt]
    ka = m if side.lower().startswith("l") else n
    a = _as_cm(a_buf, ka, lda, ka, et)
    b = _as_cm(b_buf, m, ldb, n, et)
    c = _as_cm(c_buf, m, ldc, n, et)
    out = getattr(_lp(), dt + "hemm")(
        side, uplo, m, n, alpha, np.array(a), ka, np.array(b), m,
        beta, np.array(c), m)
    c[:, :] = out
    return 0


def _rank_k_glue(fname):
    def run(dt, uplo, trans, n, k, alpha, a_buf, lda, beta, c_buf,
            ldc) -> int:
        et = _DT[dt]
        notrans = trans.lower().startswith("n")
        ra, ca = (n, k) if notrans else (k, n)
        a = _as_cm(a_buf, ra, lda, ca, et)
        c = _as_cm(c_buf, n, ldc, n, et)
        out = getattr(_lp(), dt + fname)(
            uplo, trans, n, k, alpha, np.array(a), ra, beta,
            np.array(c), n)
        c[:, :] = out
        return 0
    return run


c_syrk = _rank_k_glue("syrk")
c_herk = _rank_k_glue("herk")


def _rank_2k_glue(fname):
    def run(dt, uplo, trans, n, k, alpha, a_buf, lda, b_buf, ldb, beta,
            c_buf, ldc) -> int:
        et = _DT[dt]
        notrans = trans.lower().startswith("n")
        ra, ca = (n, k) if notrans else (k, n)
        a = _as_cm(a_buf, ra, lda, ca, et)
        b = _as_cm(b_buf, ra, ldb, ca, et)
        c = _as_cm(c_buf, n, ldc, n, et)
        out = getattr(_lp(), dt + fname)(
            uplo, trans, n, k, alpha, np.array(a), ra, np.array(b), ra,
            beta, np.array(c), n)
        c[:, :] = out
        return 0
    return run


c_syr2k = _rank_2k_glue("syr2k")
c_her2k = _rank_2k_glue("her2k")


def c_lanhe(dt, norm, uplo, n, a_buf, lda, out_buf) -> int:
    name = dt + ("lansy" if dt in "sd" else "lanhe")
    a = _as_cm(a_buf, n, lda, n, _DT[dt])
    np.frombuffer(out_buf, dtype=np.float64)[0] = float(
        getattr(_lp(), name)(norm, uplo, n, np.array(a), n))
    return 0


def c_lantr(dt, norm, uplo, diag, m, n, a_buf, lda, out_buf) -> int:
    a = _as_cm(a_buf, m, lda, n, _DT[dt])
    np.frombuffer(out_buf, dtype=np.float64)[0] = float(
        getattr(_lp(), dt + "lantr")(norm, uplo, diag, m, n,
                                     np.array(a), m))
    return 0


def c_gecon(dt, norm, n, a_buf, lda, anorm, rcond_buf) -> int:
    a = _as_cm(a_buf, n, lda, n, _DT[dt])
    rcond, info = getattr(_lp(), dt + "gecon")(norm, n, np.array(a), n,
                                               anorm)
    np.frombuffer(rcond_buf, dtype=_RDT[dt])[0] = rcond
    return int(info)


def c_pocon(dt, uplo, n, a_buf, lda, anorm, rcond_buf) -> int:
    a = _as_cm(a_buf, n, lda, n, _DT[dt])
    rcond, info = getattr(_lp(), dt + "pocon")(uplo, n, np.array(a), n,
                                               anorm)
    np.frombuffer(rcond_buf, dtype=_RDT[dt])[0] = rcond
    return int(info)


def c_trcon(dt, norm, uplo, diag, n, a_buf, lda, rcond_buf) -> int:
    a = _as_cm(a_buf, n, lda, n, _DT[dt])
    rcond, info = getattr(_lp(), dt + "trcon")(norm, uplo, diag, n,
                                               np.array(a), n)
    np.frombuffer(rcond_buf, dtype=_RDT[dt])[0] = rcond
    return int(info)


def c_hesv(dt, uplo, n, nrhs, a_buf, lda, ipiv_buf, b_buf, ldb) -> int:
    et = _DT[dt]
    name = dt + ("sysv" if dt in "sd" else "hesv")
    a = _as_cm(a_buf, n, lda, n, et)
    b = _as_cm(b_buf, n, ldb, nrhs, et)
    f, piv, x, info = getattr(_lp(), name)(
        uplo, n, nrhs, np.array(a), n, np.array(b), n)
    if info == 0:
        a[:, :] = f[:n, :n]
        np.frombuffer(ipiv_buf, dtype=np.int64)[:n] = piv[:n]
        b[:, :] = x
    return int(info)


def c_hetrf(dt, uplo, n, a_buf, lda, ipiv_buf) -> int:
    et = _DT[dt]
    name = dt + ("sytrf" if dt in "sd" else "hetrf")
    a = _as_cm(a_buf, n, lda, n, et)
    f, piv, info = getattr(_lp(), name)(uplo, n, np.array(a), n)
    a[:, :] = f[:n, :n]
    np.frombuffer(ipiv_buf, dtype=np.int64)[:n] = piv[:n]
    return int(info)


def c_hetrs(dt, uplo, n, nrhs, a_buf, lda, ipiv_buf, b_buf, ldb) -> int:
    et = _DT[dt]
    name = dt + ("sytrs" if dt in "sd" else "hetrs")
    a = _as_cm(a_buf, n, lda, n, et)
    b = _as_cm(b_buf, n, ldb, nrhs, et)
    piv = np.array(np.frombuffer(ipiv_buf, dtype=np.int64)[:n])
    x, info = getattr(_lp(), name)(
        uplo, n, nrhs, np.array(a), n, piv, np.array(b), n)
    if info == 0:
        b[:, :] = x
    return int(info)


def c_pbsv(dt, uplo, n, kd, nrhs, ab_buf, ldab, b_buf, ldb) -> int:
    et = _DT[dt]
    ab = _as_cm(ab_buf, min(ldab, kd + 1), ldab, n, et)
    b = _as_cm(b_buf, n, ldb, nrhs, et)
    x, info = getattr(_lp(), dt + "pbsv")(
        uplo, n, kd, nrhs, np.array(ab), kd + 1, np.array(b), n)
    if info == 0:
        b[:, :] = x
    return int(info)


def c_gbsv(dt, n, kl, ku, nrhs, ab_buf, ldab, ipiv_buf, b_buf,
           ldb) -> int:
    et = _DT[dt]
    ab = _as_cm(ab_buf, min(ldab, 2 * kl + ku + 1), ldab, n, et)
    b = _as_cm(b_buf, n, ldb, nrhs, et)
    x, piv, info = getattr(_lp(), dt + "gbsv")(
        n, kl, ku, nrhs, np.array(ab), 2 * kl + ku + 1, np.array(b), n)
    if info == 0:
        b[:, :] = x
        np.frombuffer(ipiv_buf, dtype=np.int64)[:n] = piv[:n]
    return int(info)


def c_trtri(dt, uplo, diag, n, a_buf, lda) -> int:
    et = _DT[dt]
    a = _as_cm(a_buf, n, lda, n, et)
    inv, info = getattr(_lp(), dt + "trtri")(uplo, diag, n, np.array(a), n)
    if info == 0:
        # LAPACK in-place contract: only the stored triangle is
        # written; the opposite triangle's data stays untouched — and
        # with DIAG='U' the diagonal is neither referenced nor
        # modified, so the caller's stored diagonal survives too
        orig = np.array(a)
        keep_diag = (np.diagonal(orig) if diag.lower().startswith("u")
                     else np.diagonal(inv))
        if uplo.lower().startswith("l"):
            a[:, :] = (np.tril(inv, -1) + np.diag(keep_diag)
                       + np.triu(orig, 1))
        else:
            a[:, :] = (np.triu(inv, 1) + np.diag(keep_diag)
                       + np.tril(orig, -1))
    return int(info)


def c_hegv(dt, itype, jobz, uplo, n, a_buf, lda, b_buf, ldb,
           w_buf) -> int:
    """Generalized Hermitian-definite eigenproblem on LAPACK buffers.

    Exit-state contract: on info=0, W holds the eigenvalues, A the
    eigenvectors (jobz='V'), and B its Cholesky factor. When the device
    solve succeeds but the host-side reconstruction of B's factor fails
    (marginally-definite B), info = 2n+1 is returned — outside LAPACK's
    1..2n failure coding, so it is distinguishable — and the exit state
    is PARTIAL: W (and A's eigenvectors) are valid, but B still holds
    the caller's original data, not its factor."""
    et = _DT[dt]
    name = dt + ("sygv" if dt in "sd" else "hegv")
    a = _as_cm(a_buf, n, lda, n, et)
    b = _as_cm(b_buf, n, ldb, n, et)
    w, z, info = getattr(_lp(), name)(
        itype, jobz, uplo, n, np.array(a), n, np.array(b), n)
    if w is None:
        return int(info if info is not None else -1)
    np.frombuffer(w_buf, dtype=_RDT[dt])[:n] = np.asarray(w)
    if z is not None:
        a[:, :] = z  # LAPACK: eigenvectors overwrite A when jobz='V'
    if int(info) == 0:
        # LAPACK exit state: B is overwritten by its Cholesky factor
        # (U or L per uplo) — callers reuse it for back-transforms
        bn = np.array(b)
        lower = uplo.lower().startswith("l")
        tri = np.tril(bn) if lower else np.triu(bn)
        herm = (tri + np.conj(tri.T)
                - np.diag(np.real(np.diagonal(tri)).astype(bn.dtype)))
        try:
            f = np.linalg.cholesky(herm.astype(
                np.complex128 if np.iscomplexobj(bn) else np.float64))
        except np.linalg.LinAlgError:
            # marginally-definite B: the device solve succeeded but the
            # stricter host factorization failed — B is left as given
            # (unmet LAPACK exit contract), flagged by the distinct
            # info = 2n+1 documented above
            return 2 * n + 1
        fac = f if lower else np.conj(f.T)
        keep = np.triu(bn, 1) if lower else np.tril(bn, -1)
        b[:, :] = (fac.astype(bn.dtype)
                   + keep)
    return int(info)


def c_gesv_nopiv(dt, n, nrhs, a_buf, lda, b_buf, ldb) -> int:
    """slate_lu_solve_nopiv analog (no LAPACK symbol — the reference
    exposes it only through the C API / slate.hh). Matches the
    reference's exit state: A is overwritten by its no-pivot LU factors
    (L unit-lower below the diagonal, U on/above) whenever the
    factorization ran, so callers can reuse the factored A; B gets the
    solution only on info=0."""
    et = _DT[dt]
    a = _as_cm(a_buf, n, lda, n, et)
    b = _as_cm(b_buf, n, ldb, nrhs, et)
    import slate_tpu as st
    from slate_tpu.core.types import MethodLU, Options
    opts = Options(method_lu=MethodLU.NoPiv)
    A = st.from_dense(np.array(a, order="C"), nb=max(16, min(256, n)))
    B = st.from_dense(np.array(b, order="C"), nb=max(16, min(256, n)))
    LU, perm, info = st.getrf(A, opts)
    a[:, :] = np.asarray(LU.to_numpy())[:n, :n]
    if int(info) == 0:
        X = st.getrs(LU, perm, B, opts)
        b[:, :] = np.asarray(X.to_numpy())[:n, :nrhs]
    return int(info)


# --- opaque matrix handles (reference analog: the generated
# slate_Matrix_create_* C API, include/slate/c_api/matrix.h +
# src/c_api/wrappers.cc) — C callers keep a device-resident TiledMatrix
# across calls instead of re-packing dense buffers per call. Solve verbs
# route through the process-wide runtime Session (slate_tpu.runtime), so
# repeated solves against the same handle reuse its resident
# factorization from the shared HBM-budget cache. ---------------------------

_HANDLES: dict = {}
_HANDLE_SEQ = [0]
_HANDLE_KEYS: dict = {}  # capi handle -> session keys registered for it


def _serve_session():
    from slate_tpu.runtime import default_session
    return default_session()


def _new_handle(M) -> int:
    _HANDLE_SEQ[0] += 1
    h = _HANDLE_SEQ[0]
    _HANDLES[h] = M
    return h


def _get_handle(h: int):
    return _HANDLES.get(int(h))


def _set_handle(h: int, M):
    """Replace a handle's resident content — any factorization the
    serving Session cached for the old content is now stale; drop it."""
    _invalidate_handle(h)
    _HANDLES[int(h)] = M


def _invalidate_handle(h: int):
    keys = _HANDLE_KEYS.pop(int(h), ())
    if keys:
        sess = _serve_session()
        for k in keys:
            sess.unregister(k)


def _session_solver(h: int, M, op: str, uplo: str = None):
    """(session, key) for solving against handle ``h``'s content,
    registering the operator with the shared Session on first use."""
    from slate_tpu.core.exceptions import SlateError
    sess = _serve_session()
    key = ("capi", int(h), op, uplo)
    if key not in sess:
        A = _handle_hermitian(M, uplo) if op == "chol" else M
        try:
            sess.register(A, op=op, handle=key)
        except SlateError:
            # a concurrent native thread won the register race — the
            # content is identical (same handle), so just use its entry
            pass
        _HANDLE_KEYS.setdefault(int(h), set()).add(key)
        cur = _HANDLES.get(int(h))
        if cur is not M:
            # the handle was rewritten (or destroyed) between our read
            # and the registration recording — the invalidation in
            # _set_handle could not see our key yet, so drop the stale
            # registration ourselves and re-resolve from current content
            sess.unregister(key)
            _HANDLE_KEYS.get(int(h), set()).discard(key)
            if cur is None:
                return sess, key  # destroyed: solve will fail cleanly
            return _session_solver(h, cur, op, uplo)
    return sess, key


def c_matrix_create(dt, m, n, nb) -> int:
    """Zero-filled m x n resident matrix; returns handle > 0."""
    import slate_tpu as st
    from .lapack_api import _nb
    nb = int(nb) or _nb(min(m, n))
    return _new_handle(st.zeros(int(m), int(n), nb, _DT[dt]))


def c_matrix_from_buffer(dt, m, n, a_buf, lda, nb) -> int:
    import slate_tpu as st
    from .lapack_api import _nb
    a = _as_cm(a_buf, m, lda, n, _DT[dt])
    nb = int(nb) or _nb(min(m, n))
    return _new_handle(st.from_dense(np.ascontiguousarray(a), nb=nb))


def c_matrix_to_buffer(dt, h, m, n, a_buf, lda) -> int:
    M = _get_handle(h)
    if M is None:
        return -1
    if tuple(M.shape) != (int(m), int(n)):
        return -2
    _as_cm(a_buf, m, lda, n, _DT[dt])[:, :] = M.to_numpy()
    return 0


def c_matrix_destroy(dt, h) -> int:
    _invalidate_handle(h)
    return 0 if _HANDLES.pop(int(h), None) is not None else -1


def c_hgemm(dt, transa, transb, alpha, ha, hb, beta, hc) -> int:
    """C_handle <- alpha op(A_handle) op(B_handle) + beta C_handle;
    all three matrices stay device-resident."""
    import slate_tpu as st
    A, B, C = _get_handle(ha), _get_handle(hb), _get_handle(hc)
    if A is None or B is None or C is None:
        return -1

    def op(M, t):
        t = t.lower()
        return M if t.startswith("n") else (M.T if t.startswith("t")
                                            else M.H)

    _set_handle(hc, st.gemm(alpha, op(A, transa), op(B, transb),
                            beta, C))
    return 0


def _handle_hermitian(M, uplo: str):
    """Uplo-triangle Hermitian/symmetric view of a handle's content
    (one shared construction — see lapack_api._hermitian_from)."""
    from .lapack_api import _hermitian_from
    return _hermitian_from(M.to_numpy(), uplo, M.shape[0], M.dtype,
                           M.nb)


def c_hposv(dt, uplo, ha, hb) -> int:
    """Solve resident-A X = resident-B; X replaces B's handle content.
    A's handle content is the dense Hermitian data (uplo triangle).
    Routed through the shared runtime Session: the Cholesky factor of A
    stays resident, so repeated solves against the same handle skip the
    factorization (cache-hit) until the handle's content changes or the
    factor is evicted under HBM pressure."""
    from slate_tpu.core.exceptions import SlateError
    A, B = _get_handle(ha), _get_handle(hb)
    if A is None or B is None:
        return -1
    sess, key = _session_solver(ha, A, "chol", uplo)
    try:
        X = sess.solve_matrix(key, B)
    except SlateError:
        # factorization failure (potrf info > 0) or solve failure; the
        # factor record is cached, so the info peek costs no access.
        # A solve failure with a clean factor returns 2n+1 — positive
        # and outside LAPACK's 1..n info range (info < 0 would falsely
        # claim an illegal argument)
        try:
            info = sess.factor_info(key)
        except SlateError:
            return -1  # handle destroyed/unregistered mid-call
        n = A.shape[0]
        return int(info) if int(info) != 0 else 2 * n + 1
    _set_handle(hb, X)
    return 0


def c_hpotrf(dt, uplo, h) -> int:
    """Factor the resident matrix in place (handle content becomes the
    triangular factor, reusable by later handle calls)."""
    import slate_tpu as st
    A = _get_handle(h)
    if A is None:
        return -1
    L, info = st.potrf(_handle_hermitian(A, uplo))
    if int(info) == 0:
        _set_handle(h, L)
    return int(info)


def c_hgesv(dt, ha, hb) -> int:
    """slate_lu_solve on handles: solve resident-A X = resident-B,
    X replaces B's content (A's content is left as given — functional
    semantics; the reference overwrites A with its LU factor). Routed
    through the shared runtime Session: A's LU factor stays resident
    across calls (see c_hposv)."""
    from slate_tpu.core.exceptions import SlateError
    A, B = _get_handle(ha), _get_handle(hb)
    if A is None or B is None:
        return -1
    sess, key = _session_solver(ha, A, "lu")
    try:
        X = sess.solve_matrix(key, B)
    except SlateError:
        # factorization failure (getrf info > 0) or solve failure; the
        # factor record is cached, so the info peek costs no access.
        # A solve failure with a clean factor returns 2n+1 — positive
        # and outside LAPACK's 1..n info range (info < 0 would falsely
        # claim an illegal argument)
        try:
            info = sess.factor_info(key)
        except SlateError:
            return -1  # handle destroyed/unregistered mid-call
        n = A.shape[0]
        return int(info) if int(info) != 0 else 2 * n + 1
    _set_handle(hb, X)
    return 0


def c_htrsm(dt, side, uplo, transa, diag, alpha, ha, hb) -> int:
    """slate_triangular_solve on handles: B <- alpha op(A)^-1 B (or
    right side); the solution replaces B's handle content. The
    triangle view is a device-side kind change (trsm masks the
    opposite triangle itself) — no host round-trip."""
    import dataclasses

    import slate_tpu as st
    from slate_tpu.core.types import Diag, MatrixKind, Side, Uplo
    A, B = _get_handle(ha), _get_handle(hb)
    if A is None or B is None:
        return -1
    u = Uplo.Lower if uplo.lower().startswith("l") else Uplo.Upper
    d = Diag.Unit if diag.lower().startswith("u") else Diag.NonUnit
    T = dataclasses.replace(A, kind=MatrixKind.Triangular, uplo=u,
                            diag=d)
    t = transa.lower()
    if not t.startswith("n"):
        T = T.T if t.startswith("t") else T.H
    s = Side.Left if side.lower().startswith("l") else Side.Right
    _set_handle(hb, st.trsm(s, alpha, T, B))
    return 0


def c_hnorm(dt, norm, h, out_buf) -> int:
    """slate_norm on a handle: Max/One/Inf/Fro of the resident matrix,
    written to out_buf[0] (real scalar of the precision)."""
    import slate_tpu as st
    from .lapack_api import _norm_of
    A = _get_handle(h)
    if A is None:
        return -1
    v = st.norm(A, _norm_of(norm))
    np.frombuffer(out_buf, dtype=_RDT[dt])[:1] = float(v)
    return 0


# --- legacy d-only aliases (pre-round-4 symbol names; kept so older
# compiled callers of c_dgesv etc. keep working) ---------------------------

def _legacy(fn, dt="d"):
    def wrap(*args):
        return fn(dt, *args)
    return wrap


c_dgesv = _legacy(c_gesv)
c_dpotrf = _legacy(c_potrf)
c_dposv = _legacy(c_posv)
c_dgels = _legacy(c_gels)
c_dgetrf = _legacy(c_getrf)
c_dgetrs = _legacy(c_getrs)
c_dpotrs = _legacy(c_potrs)
c_dsyev = _legacy(c_heev)
c_dgesvd = _legacy(c_gesvd)
c_dgemm = _legacy(c_gemm)
c_dtrsm = _legacy(c_trsm)
c_dlange = _legacy(c_lange)
