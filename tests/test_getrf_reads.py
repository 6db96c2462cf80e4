"""getrf's trailing update reads no copy of the trailing slab.

Each step of the iterative LU (``linalg/lu.py`` ``_getrf_iter``) carries
its trailing block as an array of its own, the input at step 0 and each
step's Schur block after it, and gathers the permuted rows out of it
whole: one thin gather of the pivot rows (``getrf_l<k>_load``) and one
of the rows below them (``getrf_l<k>_trail_next`` or ``_trail``). A row
gather that takes whole rows of its operand is the one XLA:TPU runs
natively (a column offset in its index lowers to a loop of one-row
slices), so no column range of the matrix is copied out first. Pinned
on the lowered gesv of the benchmark's LU cell at n=2048, nb=512, f32.

The values are those of the slab-then-gather read it replaced: the
factors, ``perm`` and ``info`` are pinned bit for bit against a per-step
reference, kept here, that copies the trailing slab and gathers its
permuted rows.
"""

import os
import re
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

from benchmark import drive, manifest  # noqa: E402

import slate_tpu as st  # noqa: E402
from slate_tpu.core.types import Options  # noqa: E402
from slate_tpu.linalg import lu as lu_mod  # noqa: E402
from slate_tpu.ops import blocked  # noqa: E402

N, NB, NRHS = 2048, 512, 16
_DEF = re.compile(r"^\s*(?:ROOT )?([\w.-]+) = \w+\[([\d,]*)\]")
_STEP = re.compile(r"/getrf_l(\d+)_(load|trail)(_next|_rest)?/")


def _dims(text):
    return tuple(int(d) for d in text.split(",") if d)


@pytest.fixture(scope="module")
def step_ops():
    """(step, scope, dims, opcode, line) of each op the lowered gesv
    runs in a step's load or trailing scopes, and the dims of every
    instruction by name."""
    cell = manifest.cell(ROOT, "lu_n16384.factor_solve")
    dtype = jnp.dtype(cell.config["dtype"])
    A = jax.eval_shape(lambda a: cell.verb.wrap(a, NB),
                       jax.ShapeDtypeStruct((N, N), dtype))
    B = jax.eval_shape(lambda b: st.from_dense(b, nb=NB),
                       jax.ShapeDtypeStruct((N, NRHS), dtype))
    opts = drive.options(cell.config, False)

    def gesv(A, B):
        return cell.verb.call(A, B, opts)

    text = jax.jit(gesv).lower(A, B).as_text(dialect="hlo", debug_info=True)
    dims, ops = {}, []
    for line in text.splitlines():
        d = _DEF.match(line)
        if not d:
            continue
        dims[d.group(1)] = _dims(d.group(2))
        step = _STEP.search(line)
        if step:
            opcode = re.search(r"\]\S*\s+([\w-]+)\(", line).group(1)
            ops.append((int(step.group(1)),
                        step.group(2) + (step.group(3) or ""),
                        dims[d.group(1)], opcode, line))
    return ops, dims


def test_trailing_reads_copy_no_slab(step_ops):
    ops, _ = step_ops
    assert ops, "the step scopes are missing"
    for k, scope, dims, opcode, line in ops:
        k0, k1 = k * NB, (k + 1) * NB
        slabs = {(N - k0, N - k1), (N - k0, N - k1 - NB), (N - k0, NB)}
        assert dims not in slabs, line


def test_trailing_gathers_take_whole_rows(step_ops):
    """Each step gathers its pivot rows under its load scope and the
    rows below them under its first trailing scope, every gather taking
    whole rows of its operand by a row index alone."""
    ops, dims = step_ops
    gathers = [(k, scope, line) for k, scope, _, opcode, line in ops
               if opcode == "gather"]
    for k, scope, line in gathers:
        operand = re.search(r" gather\(([\w.-]+),", line).group(1)
        sizes = _dims(re.search(r"slice_sizes=\{([\d,]*)\}", line).group(1))
        assert "start_index_map={0}," in line, line
        assert sizes == (1, dims[operand][1]), line
        assert "/row_swap/" in line, line
    held = {(k, scope) for k, scope, _ in gathers}
    for k in range(N // NB - 1):  # the last panel has no trailing columns
        first = "trail_next" if k + 2 < N // NB else "trail"
        assert {(k, "load"), (k, first)} <= held, k


def _reference(a, nb, prec, lookahead):
    """The iterative partial-pivot LU with the trailing read it had
    before: copy the slab a[k0:, k1:], gather its permuted rows, and
    split the update at the next panel's columns as the lookahead
    schedule does."""
    m, w = a.shape
    perm = jnp.arange(m, dtype=jnp.int32)
    info = jnp.zeros((), jnp.int32)
    pps = []
    for k in range(w // nb):
        k0, k1 = k * nb, (k + 1) * nb
        rows = m - k0
        hb = blocked.bucket_pow2(rows, nb)
        lu_p, p_p, i_p = blocked.panel_getrf_jit(
            jnp.pad(a[k0:, k0:k1], ((0, hb - rows), (0, 0))))
        lu_p, p_p = lu_p[:rows], p_p[:rows]
        info = jnp.where((info == 0) & (i_p > 0), k0 + i_p,
                         info).astype(jnp.int32)
        perm = perm.at[k0:].set(perm[k0:][p_p])
        a = blocked.dus_i32(a, lu_p, k0, k0)
        pps.append(p_p)
        if k1 >= w:
            continue
        l11 = jnp.tril(lu_p[:nb], -1) + jnp.eye(nb, dtype=a.dtype)
        inv11 = blocked.trtri_lower_batched(l11, unit=True)
        right = a[k0:, k1:]
        cuts = [0, nb, w - k1] if lookahead and k1 + nb < w else [0, w - k1]
        for c0, c1 in zip(cuts, cuts[1:]):
            top = right[p_p[:nb], c0:c1]
            u12 = blocked.mm(inv11, top, prec)
            schur = right[:, c0:c1][p_p[nb:]] - blocked.mm(
                lu_p[nb:], u12, prec)
            a = blocked.dus_i32(a, u12, k0, k1 + c0)
            a = blocked.dus_i32(a, schur, k1, k1 + c0)
    return lu_mod._apply_deferred_left_swaps(a, pps, nb), perm, info


@pytest.mark.parametrize("m,n,dtype", [
    (768, 768, np.float32), (768, 768, np.float64),
    (1024, 640, np.float64)])
@pytest.mark.parametrize("lookahead", [1, 0])
def test_getrf_matches_the_slab_gather_reference_bit_for_bit(
        m, n, dtype, lookahead):
    nb = 128
    a = np.random.default_rng(30).standard_normal((m, n)).astype(dtype)
    A = st.from_dense(a, nb=nb)
    LU, perm, info = st.getrf(A, Options(lookahead=lookahead))
    with jax.default_matmul_precision("highest"):
        lu, perm_ref, info_ref = _reference(
            jnp.asarray(a), nb, Options().update_precision, lookahead)
    assert int(info) == int(info_ref) == 0
    np.testing.assert_array_equal(np.asarray(perm), np.asarray(perm_ref))
    np.testing.assert_array_equal(np.asarray(LU.data)[:m, :n],
                                  np.asarray(lu))


def test_getrf_interchanges_rows_of_a_singular_matrix():
    """A zero column gives an exact zero pivot, and zero rows leave the
    panels choosing among fewer rows: both arms still carry the
    reference's rows, perm and first zero pivot."""
    m, nb = 512, 128
    a = np.random.default_rng(31).standard_normal((m, m))
    a[:, 300] = 0.0
    a[200:260] = 0.0
    for lookahead in (1, 0):
        LU, perm, info = st.getrf(st.from_dense(a, nb=nb),
                                  Options(lookahead=lookahead))
        with jax.default_matmul_precision("highest"):
            lu, perm_ref, info_ref = _reference(jnp.asarray(a), nb, "high",
                                                lookahead)
        assert int(info) == int(info_ref) > 0
        np.testing.assert_array_equal(np.asarray(perm), np.asarray(perm_ref))
        np.testing.assert_array_equal(np.asarray(LU.data), np.asarray(lu))
