"""CPU tests of ``layers/solve_inverse_ms.py`` on a trace reduced by
hand: the triangular sweeps' batched diagonal-block inverses are solve
time, and the reader takes them and nothing else.

    JAX_PLATFORMS=cpu python3 -m pytest benchmark/tests -q
"""

import os
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from benchmark import manifest, phases, trace  # noqa: E402

# device seconds of two calls, by scope path
SOLVE = {
    "potrs_fwd/trsm_diag_inv/dot_general": 0.002,
    "potrs_bwd/trsm_diag_inv": 0.001,
    "getrs_bwd/trsm_diag_inv/div": 0.003,
    "getrs_fwd/trsm_diag_inv_other": 0.050,
    "potrs_fwd/dot_general": 0.040,
    "getrs_fwd/row_swap": 0.004,
    "getrf_l0_panel": 0.100,
    "": 0.010,
}


@pytest.mark.parametrize("path", [
    "potrs_fwd/trsm_diag_inv/dot_general",
    "potrs_bwd/trsm_diag_inv",
    "getrs_bwd/trsm_diag_inv/div",
])
def test_the_batched_inverse_is_solve_time(path):
    assert phases.layer(path) == "solve"


def _ctx(scope_s, program="jit_gesv"):
    tr = trace.Reduced(window_s=1.0, busy_s=0.9,
                       modules={"jit_gesv": [0.4, 0.4]}, scope_s=scope_s,
                       custom_s=0.0, ops_s={}, gaps=[])
    return {"trace": tr, "program": program}


@pytest.mark.parametrize("cell", ["chol_n16384.factor_solve",
                                  "lu_n16384.factor_solve"])
def test_reader_takes_only_the_inverse(cell):
    read = manifest.cell(ROOT, cell).readers["solve_inverse_ms"]
    got = read(_ctx(SOLVE))
    assert got == pytest.approx(1e3 * 0.006 / 2)
    assert got < phases.layer_ms(_ctx(SOLVE), "solve")


def test_a_program_without_the_batched_inverse():
    """A program whose sweep leaves each invert their own block has no
    ``trsm_diag_inv`` scope: the reader finds nothing there, nor in a
    program that did not run."""
    read = manifest.cell(ROOT, "lu_n16384.factor_solve").readers[
        "solve_inverse_ms"]
    assert read(_ctx({"getrs_fwd/jit(_trtri_block)/while": 0.1})) is None
    assert read(_ctx(SOLVE, program="jit_posv")) is None
    assert read({"trace": None, "program": "jit_gesv"}) is None
