"""TiledMatrix — the distributed tiled matrix data model.

TPU-native re-design of the reference's L1/L2 storage stack:

- ``BaseMatrix`` (include/slate/BaseMatrix.hh:40, 3,976 lines of view state,
  MOSI coherency, MPI broadcast/reduce) collapses to a small immutable
  pytree: a padded dense ``jax.Array`` plus tile/view metadata. There is no
  MOSI protocol and no receive_count life-cycle — a sharded ``jax.Array``
  over a Mesh *is* the single-source-of-truth distributed matrix, and XLA
  GSPMD inserts the equivalents of tileBcast/listBcast/listReduce
  (BaseMatrix.hh:1958-2245) as all-gather/reduce-scatter/collective-permute
  over ICI when drivers request reshardings.
- ``MatrixStorage``/``TileNode``/``Memory`` (include/slate/internal/
  MatrixStorage.hh, Memory.hh) have no analog: XLA owns device memory.
- ``Tile`` (include/slate/Tile.hh:106) becomes a logical (nb, nb) slice of
  the padded storage — see tile()/with_tile().
- Matrix kinds (Matrix.hh + 10 subclasses, include/slate/*.hh) become a
  ``MatrixKind`` metadata field plus constructor helpers; band kinds carry
  (kl, ku). Round 1 stores band matrices as masked dense; packed band
  storage is a later optimization.

Semantics difference, by design: the reference's sub()/slice() return
*views that alias and mutate* the parent. JAX is functional — our sub/slice
return independent values, and drivers return new matrices instead of
mutating in place. transpose()/conj_transpose() remain zero-copy metadata
flips exactly like the reference (BaseMatrix.hh:140-148).
"""

from __future__ import annotations

import dataclasses
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

from .exceptions import SlateError
from .grid import ProcessGrid, num_tiles, tile_dim
from .types import Diag, MatrixKind, Op, Uplo


@jax.tree_util.register_pytree_node_class
@dataclasses.dataclass(frozen=True)
class TiledMatrix:
    """An (m × n) matrix stored as padded (mt·nb × nt·nb) dense data.

    ``data`` is always in NoTrans orientation; ``op`` is a view flag applied
    lazily by dense()/tile(). Padding rows/cols beyond (m, n) are zero.
    """

    data: jax.Array
    m: int
    n: int
    nb: int
    kind: MatrixKind = MatrixKind.General
    uplo: Uplo = Uplo.General
    op: Op = Op.NoTrans
    diag: Diag = Diag.NonUnit
    kl: int = 0
    ku: int = 0
    grid: Optional[ProcessGrid] = None
    # storage is 2D BLOCK-CYCLIC over the grid: storage tile-row s holds
    # logical tile-row cyclic_permutation(mt, p)[s] (ditto columns over
    # q). The ScaLAPACK-model layout (reference func::process_2d_grid,
    # include/slate/func.hh:100-120): contiguous GSPMD shards of the
    # permuted storage are exactly the cyclic tile sets, so each device
    # owns tiles {i : i mod p == pi}. dense() unpermutes to logical
    # order (one gather = collective-permute over ICI).
    cyclic: bool = False
    # factor-packing tag ("aasen", "ldl", ...): lets solvers reject a
    # factor produced under a DIFFERENT packing than they consume
    # (hetrf-RBT vs hetrs, ADVICE r4) instead of silently computing a
    # wrong X. Empty = not a tagged factor.
    packing: str = ""

    # -- pytree ----------------------------------------------------------
    def tree_flatten(self):
        meta = (self.m, self.n, self.nb, self.kind, self.uplo, self.op,
                self.diag, self.kl, self.ku, self.grid, self.cyclic,
                self.packing)
        return (self.data,), meta

    @classmethod
    def tree_unflatten(cls, meta, children):
        (data,) = children
        (m, n, nb, kind, uplo, op, diag, kl, ku, grid, cyclic,
         packing) = meta
        return cls(data, m, n, nb, kind, uplo, op, diag, kl, ku, grid,
                   cyclic, packing)

    # -- shape / tiles (op-adjusted, like BaseMatrix::m()/n()/mt()/nt()) --
    @property
    def shape(self):
        return (self.m, self.n) if self.op is Op.NoTrans else (self.n, self.m)

    @property
    def mt(self) -> int:
        """Tile-rows of the *view* (reference BaseMatrix::mt())."""
        return num_tiles(self.shape[0], self.nb)

    @property
    def nt(self) -> int:
        return num_tiles(self.shape[1], self.nb)

    @property
    def dtype(self):
        return self.data.dtype

    def tile_mb(self, i: int) -> int:
        return tile_dim(i, self.shape[0], self.nb)

    def tile_nb(self, j: int) -> int:
        return tile_dim(j, self.shape[1], self.nb)

    # -- views (zero-copy metadata flips) --------------------------------
    def transpose(self) -> "TiledMatrix":
        """Reference: slate::transpose (BaseMatrix.hh:140-148)."""
        new_op = {Op.NoTrans: Op.Trans, Op.Trans: Op.NoTrans,
                  Op.ConjTrans: Op.NoTrans}[self.op]
        conj_leftover = self.op is Op.ConjTrans  # (Aᴴ)ᵀ = conj(A)
        if conj_leftover:
            return dataclasses.replace(self, data=jnp.conj(self.data),
                                       op=new_op, uplo=self.uplo.flipped(),
                                       kl=self.ku, ku=self.kl)
        return dataclasses.replace(self, op=new_op, uplo=self.uplo.flipped(),
                                   kl=self.ku, ku=self.kl)

    def conj_transpose(self) -> "TiledMatrix":
        new_op = {Op.NoTrans: Op.ConjTrans, Op.ConjTrans: Op.NoTrans,
                  Op.Trans: Op.NoTrans}[self.op]
        if self.op is Op.Trans:  # (Aᵀ)ᴴ = conj(A)
            return dataclasses.replace(self, data=jnp.conj(self.data),
                                       op=new_op, uplo=self.uplo.flipped(),
                                       kl=self.ku, ku=self.kl)
        return dataclasses.replace(self, op=new_op, uplo=self.uplo.flipped(),
                                   kl=self.ku, ku=self.kl)

    @property
    def T(self) -> "TiledMatrix":
        return self.transpose()

    @property
    def H(self) -> "TiledMatrix":
        return self.conj_transpose()

    # -- materialization -------------------------------------------------
    def _storage_logical(self) -> jax.Array:
        """Storage in logical (NoTrans) tile order — unpermutes cyclic
        packing when present."""
        if not self.cyclic:
            return self.data
        from .grid import (cyclic_permutation, inverse_permutation,
                           tile_perm_row_indices)
        p = self.grid.p if self.grid is not None else 1
        q = self.grid.q if self.grid is not None else 1
        nb = self.nb
        mtp = self.data.shape[0] // nb
        ntp = self.data.shape[1] // nb
        ridx = tile_perm_row_indices(
            inverse_permutation(cyclic_permutation(mtp, p)), nb)
        cidx = tile_perm_row_indices(
            inverse_permutation(cyclic_permutation(ntp, q)), nb)
        return self.data[jnp.asarray(ridx)][:, jnp.asarray(cidx)]

    def dense(self) -> jax.Array:
        """Padded dense array with op applied (shape mt·nb × nt·nb of the
        view). The workhorse used by drivers; XLA fuses the transpose."""
        base = self._storage_logical()
        if self.op is Op.NoTrans:
            return base
        if self.op is Op.Trans:
            return base.T
        return jnp.conj(base).T

    def dense_canonical(self) -> jax.Array:
        """Padded dense of the view at the *canonical* size (mt·nb, nt·nb),
        cropping or zero-padding any extra grid-rounding padding (see
        shard()). Drivers use this so operand shapes always line up."""
        a = self.dense()
        rows, cols = self.mt * self.nb, self.nt * self.nb
        if a.shape == (rows, cols):
            return a
        a = a[:rows, :cols]
        if a.shape != (rows, cols):
            a = jnp.pad(a, ((0, rows - a.shape[0]), (0, cols - a.shape[1])))
        return a

    def full_dense_canonical(self) -> jax.Array:
        """full_dense() cropped/padded to the canonical (mt·nb, nt·nb)
        size — the form drivers must use so operand shapes line up
        regardless of grid-rounding padding (see shard())."""
        a = self.full_dense()
        rows, cols = self.mt * self.nb, self.nt * self.nb
        if a.shape == (rows, cols):
            return a
        a = a[:rows, :cols]
        if a.shape != (rows, cols):
            a = jnp.pad(a, ((0, rows - a.shape[0]), (0, cols - a.shape[1])))
        return a

    def to_numpy(self) -> np.ndarray:
        """Crop padding and return the logical (view-shaped) matrix."""
        mm, nn = self.shape
        return np.asarray(self.dense()[:mm, :nn])

    def to_dense(self) -> jax.Array:
        mm, nn = self.shape
        return self.dense()[:mm, :nn]

    def full_dense(self) -> jax.Array:
        """Materialize implicit structure: mirror the stored triangle for
        Symmetric/Hermitian kinds, apply unit diagonal / zero the strict
        opposite triangle for Triangular, band-mask Band kinds. Used by
        checks, norms, and drivers that need an explicit operand.

        Operates at the CANONICAL (mt·nb, nt·nb) size: grid-rounding
        padding can make raw storage non-square, and mirroring a
        non-square array would be ill-formed."""
        a = self.dense_canonical()
        npad = a.shape
        if self.kind in (MatrixKind.Symmetric, MatrixKind.Hermitian):
            tri_l = jnp.tril(a)
            tri_u = jnp.triu(a)
            if self.kind is MatrixKind.Hermitian:
                if self.uplo is Uplo.Lower:
                    a = tri_l + jnp.conj(jnp.tril(a, -1)).T
                else:
                    a = tri_u + jnp.conj(jnp.triu(a, 1)).T
                # force real diagonal for Hermitian
                if jnp.iscomplexobj(a):
                    d = jnp.real(jnp.diagonal(a))
                    a = a - jnp.diag(jnp.diagonal(a)) + jnp.diag(d).astype(a.dtype)
            else:
                if self.uplo is Uplo.Lower:
                    a = tri_l + jnp.tril(a, -1).T
                else:
                    a = tri_u + jnp.triu(a, 1).T
        elif self.kind in (MatrixKind.Triangular, MatrixKind.Trapezoid,
                           MatrixKind.TriangularBand):
            a = jnp.tril(a) if self.uplo is Uplo.Lower else jnp.triu(a)
            if self.diag is Diag.Unit:
                eye = jnp.eye(npad[0], npad[1], dtype=a.dtype)
                a = a - jnp.diag(jnp.diagonal(a)) + eye
        if self.kind in (MatrixKind.Band, MatrixKind.TriangularBand,
                         MatrixKind.HermitianBand):
            kl = self.kl if self.uplo in (Uplo.General, Uplo.Lower) else 0
            ku = self.ku if self.uplo in (Uplo.General, Uplo.Upper) else 0
            if self.kind is MatrixKind.HermitianBand:
                kl = ku = self.kl or self.ku
            r = jnp.arange(npad[0])[:, None]
            c = jnp.arange(npad[1])[None, :]
            mask = (c - r <= ku) & (r - c <= kl)
            a = jnp.where(mask, a, jnp.zeros((), a.dtype))
            if self.kind is MatrixKind.HermitianBand:
                a = jnp.tril(a) + jnp.conj(jnp.tril(a, -1)).T if self.uplo is Uplo.Lower \
                    else jnp.triu(a) + jnp.conj(jnp.triu(a, 1)).T
        return a

    # -- tiles -----------------------------------------------------------
    def tile(self, i: int, j: int) -> jax.Array:
        """The (nb, nb) padded tile at tile-index (i, j) of the view.

        Reference: BaseMatrix::operator()(i, j) returning a Tile
        (include/slate/Tile.hh:106). Static slice when i, j are Python ints.
        """
        a = self.dense()
        nb = self.nb
        return jax.lax.slice(a, (i * nb, j * nb), ((i + 1) * nb, (j + 1) * nb))

    def with_tile(self, i: int, j: int, val: jax.Array) -> "TiledMatrix":
        if self.op is not Op.NoTrans:
            raise SlateError("with_tile requires a NoTrans view")
        if self.cyclic:
            raise SlateError("with_tile requires contiguous (non-cyclic) "
                             "storage; use shard(grid) first")
        data = jax.lax.dynamic_update_slice(self.data, val.astype(self.dtype),
                                            (i * self.nb, j * self.nb))
        return dataclasses.replace(self, data=data)

    def with_data(self, data: jax.Array) -> "TiledMatrix":
        return dataclasses.replace(self, data=data)

    # -- sub-matrix ------------------------------------------------------
    def sub(self, i1: int, i2: int, j1: int, j2: int) -> "TiledMatrix":
        """Tile-index sub-matrix, inclusive ranges like the reference
        (BaseMatrix::sub, BaseMatrix.hh:sub). Returns an independent value
        (functional semantics), kind demoted to General/Trapezoid rules
        are the caller's business."""
        nb = self.nb
        a = self.dense()
        i2 = min(i2, self.mt - 1)
        j2 = min(j2, self.nt - 1)
        if i2 < i1 or j2 < j1:
            rows = max(0, i2 - i1 + 1) * nb
            cols = max(0, j2 - j1 + 1) * nb
            return TiledMatrix(jnp.zeros((rows, cols), self.dtype), 0, 0, nb,
                               grid=self.grid)
        block = a[i1 * nb:(i2 + 1) * nb, j1 * nb:(j2 + 1) * nb]
        mm, nn = self.shape
        sub_m = min(mm, (i2 + 1) * nb) - i1 * nb
        sub_n = min(nn, (j2 + 1) * nb) - j1 * nb
        return TiledMatrix(block, sub_m, sub_n, nb, kind=MatrixKind.General,
                           grid=self.grid)

    def slice(self, row1: int, row2: int, col1: int, col2: int) -> "TiledMatrix":
        """Element-index slice (inclusive), re-tiled from offset 0.

        Reference: BaseMatrix::slice (BaseMatrix.hh:770-773 offsets). We
        re-pack instead of keeping offsets — one XLA slice+pad."""
        sub_m = row2 - row1 + 1
        sub_n = col2 - col1 + 1
        a = self.to_dense()[row1:row2 + 1, col1:col2 + 1]
        return from_dense(a, self.nb, grid=self.grid, logical_shape=(sub_m, sub_n))

    # -- sharding --------------------------------------------------------
    def shard(self, grid: ProcessGrid, spec: Optional[P] = None,
              cyclic: bool = False) -> "TiledMatrix":
        """Place storage on the grid with rows over 'p', cols over 'q'.

        The analog of constructing a matrix with process_2d_grid tileRank
        lambdas (func.hh:100-120). GSPMD requires even shards, so storage
        is padded up to tile counts divisible by (p, q) — the moral
        equivalent of ScaLAPACK's padded local arrays.

        cyclic=True packs tiles 2D block-cyclically before sharding
        (see the ``cyclic`` field): device (pi, qi) then owns exactly
        the ScaLAPACK tile set {(i, j) : i mod p = pi, j mod q = qi}."""
        from .grid import cyclic_permutation, tile_perm_row_indices
        spec = spec if spec is not None else grid.spec_2d()
        nb = self.nb
        data = self._storage_logical()
        rows = -(-data.shape[0] // (grid.p * nb)) * grid.p * nb
        cols = -(-data.shape[1] // (grid.q * nb)) * grid.q * nb
        if (rows, cols) != data.shape:
            data = jnp.pad(data, ((0, rows - data.shape[0]),
                                  (0, cols - data.shape[1])))
        if cyclic:
            ridx = tile_perm_row_indices(
                cyclic_permutation(rows // nb, grid.p), nb)
            cidx = tile_perm_row_indices(
                cyclic_permutation(cols // nb, grid.q), nb)
            data = data[jnp.asarray(ridx)][:, jnp.asarray(cidx)]
        data = jax.device_put(data, NamedSharding(grid.mesh, spec))
        return dataclasses.replace(self, data=data, grid=grid,
                                   cyclic=cyclic)

    def constrain(self, spec: P) -> "TiledMatrix":
        """with_sharding_constraint under jit (needs self.grid)."""
        if self.grid is None:
            return self
        data = jax.lax.with_sharding_constraint(
            self.data, NamedSharding(self.grid.mesh, spec))
        return dataclasses.replace(self, data=data)


# ---------------------------------------------------------------------------
# Constructors (analog of Matrix::fromLAPACK / emptyLike / insertLocalTiles,
# include/slate/Matrix.hh:58-164, and the kind subclasses)
# ---------------------------------------------------------------------------


def _pad_to_tiles(a: jax.Array, nb: int) -> jax.Array:
    m, n = a.shape
    mp = num_tiles(m, nb) * nb
    np_ = num_tiles(n, nb) * nb
    if mp == m and np_ == n:
        return a
    return jnp.pad(a, ((0, mp - m), (0, np_ - n)))


def from_dense(a, nb: int, grid: Optional[ProcessGrid] = None,
               kind: MatrixKind = MatrixKind.General,
               uplo: Uplo = Uplo.General, diag: Diag = Diag.NonUnit,
               kl: int = 0, ku: int = 0,
               logical_shape=None) -> TiledMatrix:
    """Build a TiledMatrix from a dense array (host or device).

    The analog of Matrix::fromLAPACK (include/slate/Matrix.hh:58): wraps
    user data in the tiled/distributed structure. Data is padded to whole
    tiles with zeros.
    """
    a = jnp.asarray(a)
    if a.ndim != 2:
        raise SlateError("from_dense expects a 2-D array")
    m, n = logical_shape if logical_shape is not None else a.shape
    a = _pad_to_tiles(a, nb)
    if logical_shape is not None and (m < a.shape[0] or n < a.shape[1]):
        # invariant: storage beyond the logical shape is zero (drivers
        # rely on it — e.g. trsm's unit-padded diagonal, solves with
        # zero-padded rhs)
        r = jnp.arange(a.shape[0])[:, None] < m
        c = jnp.arange(a.shape[1])[None, :] < n
        a = jnp.where(r & c, a, jnp.zeros((), a.dtype))
    t = TiledMatrix(a, m, n, nb, kind=kind, uplo=uplo, diag=diag, kl=kl, ku=ku,
                    grid=grid)
    if grid is not None:
        t = t.shard(grid)
    return t


def zeros(m: int, n: int, nb: int, dtype=jnp.float32,
          grid: Optional[ProcessGrid] = None, **kw) -> TiledMatrix:
    mp = num_tiles(m, nb) * nb
    np_ = num_tiles(n, nb) * nb
    t = TiledMatrix(jnp.zeros((mp, np_), dtype), m, n, nb, grid=grid, **kw)
    if grid is not None:
        t = t.shard(grid)
    return t


def empty_like(a: TiledMatrix, m: Optional[int] = None, n: Optional[int] = None,
               dtype=None) -> TiledMatrix:
    """Reference: BaseMatrix::emptyLike (Matrix.hh:117)."""
    mm = m if m is not None else a.shape[0]
    nn = n if n is not None else a.shape[1]
    return zeros(mm, nn, a.nb, dtype or a.dtype, grid=a.grid)


def triangular(a, nb: int, uplo: Uplo, diag: Diag = Diag.NonUnit,
               grid=None) -> TiledMatrix:
    """TriangularMatrix analog (include/slate/TriangularMatrix.hh)."""
    return from_dense(a, nb, grid=grid, kind=MatrixKind.Triangular, uplo=uplo,
                      diag=diag)


def symmetric(a, nb: int, uplo: Uplo, grid=None) -> TiledMatrix:
    return from_dense(a, nb, grid=grid, kind=MatrixKind.Symmetric, uplo=uplo)


def hermitian(a, nb: int, uplo: Uplo, grid=None) -> TiledMatrix:
    return from_dense(a, nb, grid=grid, kind=MatrixKind.Hermitian, uplo=uplo)


def band(a, nb: int, kl: int, ku: int, grid=None) -> TiledMatrix:
    """BandMatrix analog (include/slate/BandMatrix.hh). Round 1: masked
    dense storage."""
    return from_dense(a, nb, grid=grid, kind=MatrixKind.Band, kl=kl, ku=ku)


def hermitian_band(a, nb: int, kd: int, uplo: Uplo, grid=None) -> TiledMatrix:
    return from_dense(a, nb, grid=grid, kind=MatrixKind.HermitianBand,
                      uplo=uplo, kl=kd, ku=kd)


def triangular_band(a, nb: int, kd: int, uplo: Uplo, diag: Diag = Diag.NonUnit,
                    grid=None) -> TiledMatrix:
    kl, ku = (kd, 0) if uplo is Uplo.Lower else (0, kd)
    return from_dense(a, nb, grid=grid, kind=MatrixKind.TriangularBand,
                      uplo=uplo, diag=diag, kl=kl, ku=ku)


def pad_mask(t: TiledMatrix) -> jax.Array:
    """Boolean mask of logical (non-padding) entries at the canonical
    padded size (matches full_dense())."""
    mm, nn = t.shape
    r = jnp.arange(t.mt * t.nb)[:, None] < mm
    c = jnp.arange(t.nt * t.nb)[None, :] < nn
    return r & c


def unit_pad_diag(a: jax.Array, m_log: int, n_log: int) -> jax.Array:
    """Set 1 on the diagonal of the padding region (rows/cols beyond the
    logical (m_log, n_log)). The single shared helper behind every
    factorization's 'padded system is block-diag [[A,0],[0,I]]' trick
    (SURVEY §7 risk (v)). Without padding on the diagonal (static
    shapes) a is returned as it is: rewriting its diagonal onto itself
    would still cost a full-size copy."""
    if min(a.shape) <= min(m_log, n_log):
        return a
    idx = jnp.arange(min(a.shape))
    d = jnp.diagonal(a)[: idx.size]
    on_pad = (idx >= m_log) | (idx >= n_log)
    return a.at[idx, idx].set(jnp.where(on_pad, jnp.ones((), a.dtype), d))


def pad_diag_identity(t: TiledMatrix) -> TiledMatrix:
    """Put 1 on the padded part of the diagonal so factorizations of the
    padded storage stay well-defined (SURVEY §7 risk (v)). The padding is
    cropped away by to_dense(), and zero rhs padding keeps solves exact."""
    if t.cyclic:
        raise SlateError("pad_diag_identity requires contiguous storage")
    return t.with_data(unit_pad_diag(t.data, t.m, t.n))
