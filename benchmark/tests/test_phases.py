"""CPU tests of the phase readers (``benchmark/phases.py`` and the four
``layers/`` files that use it) on a trace reduced by hand.

    JAX_PLATFORMS=cpu python3 -m pytest benchmark/tests -q
"""

import os
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from benchmark import manifest, phases, scopes, trace  # noqa: E402

READERS = ("solve_sweep_ms", "layout_ms", "row_permute_ms", "unscoped_ms")


@pytest.mark.parametrize("path, layer", [
    ("potrf_l0_tile", "panel"),
    ("potrf_l3_tile_lookahead/cond/branch_1_fun", "panel"),
    ("getrf_l2_panel_lookahead/row_swap", "panel"),
    ("getrf_l2_trail_rest/row_swap", "trail"),
    ("potrf_l1_trail_next", "trail"),
    ("potrs_fwd/while/body/closed_call", "solve"),
    ("getrs_fwd/row_swap", "solve"),
    ("getrs_bwd", "solve"),
    ("potrf_prologue", "layout"),
    ("potrf_epilogue", "layout"),
    ("getrf_epilogue/row_swap", "layout"),
    ("getrf_l4_store/row_swap", "layout"),
    ("getrf_l4_load", "layout"),
    ("", "unscoped"),
    ("row_swap", "unscoped"),
    ("while/body/closed_call", "unscoped"),
    ("getrf_l4_stored", "unscoped"),
])
def test_layer_of_a_scope_path(path, layer):
    assert phases.layer(path) == layer
    if layer in ("panel", "trail"):
        assert scopes.kind(path) == layer


def _reduced(scope_s, runs=2):
    return trace.Reduced(window_s=1.0, busy_s=0.9,
                         modules={"jit_gesv": [0.4] * runs},
                         scope_s=scope_s, custom_s=0.0, ops_s={}, gaps=[])


# device seconds of two gesv calls, by scope path
GESV = {
    "getrf_l0_panel": 0.100,
    "getrf_l0_panel/row_swap": 0.040,
    "getrf_l1_panel_lookahead/row_swap": 0.020,
    "getrf_l0_trail_rest": 0.060,
    "getrf_l0_trail_rest/row_swap": 0.010,
    "getrs_fwd/while/body/closed_call": 0.030,
    "getrs_fwd/row_swap": 0.002,
    "getrs_bwd": 0.028,
    "getrf_prologue": 0.004,
    "getrf_l0_load": 0.008,
    "getrf_l0_load/row_swap": 0.002,
    "getrf_l0_store": 0.006,
    "getrf_epilogue/row_swap": 0.012,
    "row_swap": 0.001,
    "": 0.017,
}


def _read(scope_s, cell="lu_n16384.factor_solve", program="jit_gesv"):
    cell = manifest.cell(ROOT, cell)
    ctx = {"trace": _reduced(scope_s), "program": program}
    return {m: cell.readers[m](ctx) for m in READERS
            if m in cell.readers}


def test_phase_readers_per_call():
    got = _read(GESV)
    assert got["solve_sweep_ms"] == pytest.approx(1e3 * 0.060 / 2)
    assert got["layout_ms"] == pytest.approx(1e3 * 0.032 / 2)
    assert got["row_permute_ms"] == pytest.approx(1e3 * 0.087 / 2)
    assert got["unscoped_ms"] == pytest.approx(1e3 * 0.018 / 2)


def test_phases_partition_the_call():
    """Panel, trail, solve, layout and unscoped add up to all device
    time of the program; the row swaps are a subset across them."""
    ctx = {"trace": _reduced(GESV), "program": "jit_gesv"}
    parts = sum(phases.layer_ms(ctx, w) or 0.0 for w in phases.LAYERS)
    assert parts == pytest.approx(1e3 * sum(GESV.values()) / 2)
    assert scopes.per_call_ms(ctx, "panel") == pytest.approx(
        phases.layer_ms(ctx, "panel"))
    assert scopes.per_call_ms(ctx, "trail") == pytest.approx(
        phases.layer_ms(ctx, "trail"))
    assert phases.row_swap_ms(ctx) < parts


def test_a_program_without_phase_scopes():
    """A program whose drivers name only their steps (the commit before
    these scopes) reads no solve, layout or row-permute time; the rest
    of its time is unscoped."""
    got = _read({"getrf_l0_panel": 0.1, "getrf_l0_trail": 0.2,
                 "": 0.3, "while/body": 0.1})
    assert got == {"solve_sweep_ms": None, "layout_ms": None,
                   "row_permute_ms": None,
                   "unscoped_ms": pytest.approx(200.0)}


def test_a_program_that_did_not_run():
    assert set(_read(GESV, program="jit_posv").values()) == {None}
    cell = manifest.cell(ROOT, "chol_n16384.factor_solve")
    assert cell.readers["unscoped_ms"]({"trace": None,
                                        "program": "jit_posv"}) is None


def test_row_permute_only_where_rows_move():
    """Cholesky interchanges no rows: its cell does not list the
    metric."""
    chol = manifest.cell(ROOT, "chol_n16384.factor_solve")
    lu = manifest.cell(ROOT, "lu_n16384.factor_solve")
    assert "row_permute_ms" not in chol.readers
    assert set(READERS) <= set(lu.readers)
    assert set(READERS) - {"row_permute_ms"} <= set(chol.readers)
