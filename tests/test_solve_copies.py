"""The triangular solves make no full-size copy of their factor.

posv's two sweeps (``potrs_fwd``/``potrs_bwd``) and gesv's
(``getrs_fwd``/``getrs_bwd``) read the factor as stored: no masked
``tril``/``triu``, no transposed factor, no unit-pad scatter onto an
unpadded diagonal. Pinned on the lowered programs of the benchmark's
verbs at n=2048, nb=512, f32: no op inside those scopes outputs an array
of the factor's (n, n) shape (a ``call`` to a nested jit counts, as the
mask's ``jit(tril)`` was one), and the only scatters there are the row
writes of the batched diagonal-block inverses (``trsm_diag_inv``) on
their leaf stacks.
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
from slate_tpu.core.tiled_matrix import unit_pad_diag  # noqa: E402

N, NB, NRHS = 2048, 512, 16
CELLS = {"posv": ("chol_n16384.factor_solve", "potrs_"),
         "gesv": ("lu_n16384.factor_solve", "getrs_")}
_INSTR = re.compile(r"=\s+(\S+)\s+([\w-]+)\(.*op_name=\"([^\"]*)\"")


def _solve_ops(verb):
    """(shape, opcode, op_name) of each op the lowered program runs in
    its solve scopes."""
    name, scope = CELLS[verb]
    cell = manifest.cell(ROOT, name)
    dtype = jnp.dtype(cell.config["dtype"])
    A = jax.eval_shape(lambda a: cell.verb.wrap(a, NB),
                       jax.ShapeDtypeStruct((N, N), dtype))
    B = jax.eval_shape(lambda b: st.from_dense(b, nb=NB),
                       jax.ShapeDtypeStruct((N, NRHS), dtype))
    opts = drive.options(cell.config, False)

    def call(A, B):
        return cell.verb.call(A, B, opts)

    call.__name__ = verb
    text = jax.jit(call).lower(A, B).as_text(dialect="hlo", debug_info=True)
    ops = [m.groups() for m in map(_INSTR.search, text.splitlines()) if m]
    return [op for op in ops if f"/{scope}" in op[2]]


def factor_copies(ops):
    """Full-size copies of the factor: ops whose output has its shape."""
    return [op for op in ops if re.match(rf"\w+\[{N},{N}\]", op[0])]


@pytest.mark.parametrize("verb", sorted(CELLS))
def test_solve_makes_no_full_size_copy_of_the_factor(verb):
    ops = _solve_ops(verb)
    assert ops, "the solve scopes are missing"
    assert factor_copies(ops) == []
    scatters = [op for op in ops if op[1] == "scatter"]
    assert all("/trsm_diag_inv/" in op[2] for op in scatters), scatters


def test_unit_pad_diag_returns_an_unpadded_operand_as_it_is():
    a = jnp.arange(64.0).reshape(8, 8)
    assert unit_pad_diag(a, 8, 8) is a
    wide = a[:6]  # a 6x8 operand: its diagonal ends inside (6, 8)
    assert unit_pad_diag(wide, 6, 8) is wide


def test_unit_pad_diag_sets_one_on_the_padded_diagonal():
    a = jnp.arange(64.0).reshape(8, 8) + 100.0
    out = np.asarray(unit_pad_diag(a, 5, 5))
    want = np.asarray(a).copy()
    want[[5, 6, 7], [5, 6, 7]] = 1.0
    np.testing.assert_array_equal(out, want)
    # a wide logical shape pads the diagonal from its shorter side
    out = np.asarray(unit_pad_diag(a, 8, 6))
    want = np.asarray(a).copy()
    want[[6, 7], [6, 7]] = 1.0
    np.testing.assert_array_equal(out, want)
