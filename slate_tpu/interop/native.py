"""ctypes bindings for the native host runtime (native/layout.cc).

Reference analog: the scalapack_api/ + lapack_api/ interchange layers and
BaseMatrix's layout-conversion machinery. The shared library is built on
first use with the repo's Makefile (g++ -fopenmp), under a file name
keyed on a hash of the committed sources — a library built from any
other sources (an older tree, a copied checkout) is never loaded. If no
compiler is available, every entry point falls back to an equivalent
numpy path so the framework stays importable (reference behavior: the
APIs are optional CMake components, CMakeLists.txt:56). The fallback is
LOGGED once (logging.warning) so a perf-relevant degradation can't pass
silently.

Round 5: all packers are dtype-generic — f32/f64/c64/c128 dispatch into
the element-size-templated native kernels (st_*_e symbols), matching the
reference's four-precision scalapack_api surface
(scalapack_api/scalapack_potrf.cc:44-110).
"""

from __future__ import annotations

import ctypes
import hashlib
import logging
import os
import subprocess
import threading
from typing import Optional

import numpy as np

_LOG = logging.getLogger("slate_tpu.interop")

_LOCK = threading.Lock()
_LIB: Optional[ctypes.CDLL] = None
_TRIED = False

_NATIVE_DIR = os.path.join(os.path.dirname(os.path.dirname(
    os.path.dirname(os.path.abspath(__file__)))), "native")
_SOURCES = ("layout.cc", "steqr.cc", "Makefile")

_I64 = ctypes.c_int64
_PV = ctypes.c_void_p
_PD = np.ctypeslib.ndpointer(np.float64, flags="C_CONTIGUOUS")

# dtypes the native kernels move (esize dispatch); everything else uses
# the numpy fallback paths
_NATIVE_DTYPES = {
    np.dtype(np.float32): 4,
    np.dtype(np.float64): 8,
    np.dtype(np.complex64): 8,    # any 8-byte POD moves identically
    np.dtype(np.complex128): 16,
}


def _so_path() -> str:
    """native/libslate_tpu_host.<hash of _SOURCES>.so"""
    h = hashlib.sha256()
    for f in _SOURCES:
        with open(os.path.join(_NATIVE_DIR, f), "rb") as fh:
            h.update(fh.read())
    return os.path.join(_NATIVE_DIR,
                        f"libslate_tpu_host.{h.hexdigest()[:16]}.so")


def _build(so: str) -> bool:
    """Build ``so`` from the sources; the rename makes concurrent
    builders (test workers) each publish a whole file."""
    tmp = f"{so}.{os.getpid()}.tmp"
    name = os.path.basename(tmp)
    try:
        subprocess.run(["make", "-C", _NATIVE_DIR, name,
                        f"TARGET={name}"], check=True, capture_output=True)
        os.replace(tmp, so)
        return True
    except (OSError, subprocess.CalledProcessError):
        return False


def get_lib() -> Optional[ctypes.CDLL]:
    """Load (building if needed) the native library; None if unavailable
    (logged once — the numpy fallback is slower, not wrong)."""
    global _LIB, _TRIED
    with _LOCK:
        if _LIB is not None or _TRIED:
            return _LIB
        _TRIED = True
        try:
            so = _so_path()
        except OSError:
            so = None  # sources absent: an installed package, no build
        if so is None or (not os.path.exists(so) and not _build(so)):
            _LOG.warning(
                "native layout library unavailable (no compiler or build "
                "failed); interop packers fall back to numpy — correct "
                "but slower")
            return None
        symbols = [
            ("st_numroc", [_I64, _I64, _I64, _I64]),
            # element-size generic entry points (round 5)
            ("st_bc_pack_e", [_PV, _I64, _I64, _I64, _I64, _I64, _I64,
                              _I64, _I64, _PV, _I64, _I64]),
            ("st_bc_unpack_e", [_PV, _I64, _I64, _I64, _I64, _I64, _I64,
                                _I64, _I64, _PV, _I64, _I64]),
            ("st_tile_pack_e", [_PV, _I64, _I64, _I64, _I64, _PV, _I64]),
            ("st_tile_unpack_e", [_PV, _I64, _I64, _I64, _I64, _PV,
                                  _I64]),
            ("st_colmajor_to_rowmajor_e", [_PV, _I64, _I64, _I64, _PV,
                                           _I64, _I64]),
            ("st_rowmajor_to_colmajor_e", [_PV, _I64, _I64, _I64, _PV,
                                           _I64, _I64]),
            # f64 compatibility names (older callers)
            ("st_bc_pack", [_PD, _I64, _I64, _I64, _I64, _I64, _I64, _I64,
                            _I64, _PD, _I64]),
            ("st_bc_unpack", [_PD, _I64, _I64, _I64, _I64, _I64, _I64,
                              _I64, _I64, _PD, _I64]),
            ("st_tile_pack", [_PD, _I64, _I64, _I64, _I64, _PD]),
            ("st_tile_unpack", [_PD, _I64, _I64, _I64, _I64, _PD]),
            ("st_colmajor_to_rowmajor", [_PD, _I64, _I64, _I64, _PD,
                                         _I64]),
            ("st_rowmajor_to_colmajor", [_PD, _I64, _I64, _I64, _PD,
                                         _I64]),
            ("st_steqr", [_I64, _PD, _PD, _PD, _I64, _I64]),
        ]

        def _load():
            try:
                lib = ctypes.CDLL(so)
            except OSError:
                return None
            for name, argtypes in symbols:
                fn = getattr(lib, name, None)
                if fn is None:
                    return None  # stale build missing a symbol
                fn.argtypes = argtypes
                fn.restype = _I64
            return lib

        _LIB = _load()
        if _LIB is None:
            _LOG.warning(
                "native layout library failed to load (unlinkable %s); "
                "interop packers fall back to numpy — correct but "
                "slower", so)
        return _LIB


def have_native() -> bool:
    return get_lib() is not None


def _esize(dtype) -> Optional[int]:
    """Native element size for ``dtype`` (None → numpy fallback only)."""
    return _NATIVE_DTYPES.get(np.dtype(dtype))


def _vp(a: np.ndarray):
    return a.ctypes.data_as(_PV)


# -- numpy fallbacks (same layout contracts as layout.cc) -------------------

def numroc(m: int, nb: int, pi: int, p: int) -> int:
    """ScaLAPACK numroc (source process 0): rows of grid coord pi of p."""
    nblocks = m // nb
    loc = (nblocks // p) * nb
    extra = nblocks % p
    if pi < extra:
        loc += nb
    elif pi == extra:
        loc += m % nb
    return loc


def _cyclic_indices(m: int, nb: int, pi: int, p: int) -> np.ndarray:
    """Global row indices owned by grid coord pi, in local-row order."""
    mt = -(-m // nb)
    blocks = np.arange(pi, mt, p, dtype=np.int64)
    idx = (blocks[:, None] * nb + np.arange(nb, dtype=np.int64)).ravel()
    return idx[idx < m]


def bc_pack(global_rm: np.ndarray, nb: int, p: int, q: int, pi: int,
            qi: int) -> np.ndarray:
    """Global row-major (m, n) → this process's TRUE ScaLAPACK local
    array: column-major (mloc, nloc) with mloc = numroc(m, nb, pi, p),
    byte-compatible with BLACS/ScaLAPACK local buffers (lld = mloc).
    Keeps the input dtype (s/d/c/z all native-packed)."""
    a = np.ascontiguousarray(global_rm)
    m, n = a.shape
    mloc, nloc = numroc(m, nb, pi, p), numroc(n, nb, qi, q)
    lib, es = get_lib(), _esize(a.dtype)
    if lib is not None and es is not None:
        flat = np.zeros(mloc * nloc, a.dtype)
        rc = lib.st_bc_pack_e(_vp(a), m, n, a.strides[0] // a.itemsize,
                              nb, p, q, pi, qi, _vp(flat), mloc, es)
        if rc == 0:
            return flat.reshape((mloc, nloc), order="F")
    gr = _cyclic_indices(m, nb, pi, p)
    gc = _cyclic_indices(n, nb, qi, q)
    return np.asfortranarray(a[np.ix_(gr, gc)])


def bc_unpack(local: np.ndarray, m: int, n: int, nb: int, p: int, q: int,
              pi: int, qi: int, out: Optional[np.ndarray] = None,
              lld: Optional[int] = None) -> np.ndarray:
    """Scatter a ScaLAPACK column-major local array into the global
    row-major matrix (writes only this process's entries).

    ``local`` may be a (lld, nloc) 2-D array (any memory order; rows
    beyond mloc are the unused lld slack) or a flat column-major buffer
    with ``lld`` given."""
    loc = np.asarray(local)
    if out is None:
        out = np.zeros((m, n), loc.dtype)
    mloc, nloc = numroc(m, nb, pi, p), numroc(n, nb, qi, q)
    loc = np.asarray(loc, dtype=out.dtype)
    if loc.ndim == 1:
        ld = lld if lld is not None else mloc
        loc = loc.reshape((ld, nloc), order="F")
    loc = loc[:mloc, :nloc]
    if loc.shape != (mloc, nloc):
        raise ValueError(
            f"bc_unpack: local buffer {np.asarray(local).shape} too small "
            f"for numroc sizes ({mloc}, {nloc})")
    lib, es = get_lib(), _esize(out.dtype)
    if lib is not None and es is not None and out.flags.c_contiguous:
        locf = np.asfortranarray(loc)
        rc = lib.st_bc_unpack_e(_vp(locf), m, n,
                                out.strides[0] // out.itemsize, nb, p, q,
                                pi, qi, _vp(out), mloc, es)
        if rc == 0:
            return out
    gr = _cyclic_indices(m, nb, pi, p)
    gc = _cyclic_indices(n, nb, qi, q)
    out[np.ix_(gr, gc)] = loc
    return out


def tile_pack(global_rm: np.ndarray, nb: int) -> np.ndarray:
    a = np.ascontiguousarray(global_rm)
    m, n = a.shape
    mt, nt = -(-m // nb), -(-n // nb)
    out = np.zeros((mt, nt, nb, nb), a.dtype)
    lib, es = get_lib(), _esize(a.dtype)
    if lib is not None and es is not None:
        rc = lib.st_tile_pack_e(_vp(a), m, n, a.strides[0] // a.itemsize,
                                nb, _vp(out), es)
        if rc == 0:
            return out
    for i in range(mt):
        for j in range(nt):
            r0, c0 = i * nb, j * nb
            rows, cols = min(nb, m - r0), min(nb, n - c0)
            out[i, j, :rows, :cols] = a[r0:r0 + rows, c0:c0 + cols]
    return out


def tile_unpack(tiles: np.ndarray, m: int, n: int) -> np.ndarray:
    t = np.ascontiguousarray(tiles)
    mt, nt, nb, _ = t.shape
    out = np.zeros((m, n), t.dtype)
    lib, es = get_lib(), _esize(t.dtype)
    if lib is not None and es is not None:
        rc = lib.st_tile_unpack_e(_vp(t), m, n,
                                  out.strides[0] // out.itemsize, nb,
                                  _vp(out), es)
        if rc == 0:
            return out
    for i in range(mt):
        for j in range(nt):
            r0, c0 = i * nb, j * nb
            rows, cols = min(nb, m - r0), min(nb, n - c0)
            out[r0:r0 + rows, c0:c0 + cols] = t[i, j, :rows, :cols]
    return out


def colmajor_to_rowmajor(cm: np.ndarray) -> np.ndarray:
    a = np.asfortranarray(cm)
    m, n = a.shape
    out = np.empty((m, n), a.dtype)
    lib, es = get_lib(), _esize(a.dtype)
    if lib is not None and es is not None:
        rc = lib.st_colmajor_to_rowmajor_e(_vp(a), m, n, m, _vp(out), n,
                                           es)
        if rc == 0:
            return out
    return np.ascontiguousarray(cm)
