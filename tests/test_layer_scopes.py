"""Every op of the benchmarked posv and gesv programs lands in a phase.

The drivers (``linalg/cholesky.py``, ``linalg/lu.py``) and the solves
name each phase with ``jax.named_scope``; ``benchmark/phases.py`` turns
a scope path into the phase its per-layer metric reads. Compiled here at
the benchmark's rehearsal sizes (n=256, nb=64: the outer loop takes four
steps) with 16 right-hand sides, and indexed as the trace reduction
indexes the chip's program (``benchmark.trace.hlo_index``).
"""

import os
import re
import sys

import jax
import jax.numpy as jnp
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

from benchmark import drive, manifest, phases, scopes, trace  # noqa: E402

N, NB, NRHS = drive.REHEARSAL["n"], drive.REHEARSAL["nb"], 16
CELLS = {"posv": "chol_n16384.factor_solve", "gesv": "lu_n16384.factor_solve"}
# the step scopes the panel-chain and trailing-update metrics read; a
# phase scope never takes one of their names
STEP_NAMES = re.compile(
    r"^(potrf|getrf)_l\d+_(tile|panel|trail)(_lookahead|_next|_rest)?$")
PHASES = ("panel", "trail", "solve", "layout")


@pytest.fixture(scope="module")
def program():
    """verb -> instruction name -> op_name of its compiled program."""
    import slate_tpu as st

    compiled = {}

    def get(verb):
        if verb not in compiled:
            cell = manifest.cell(ROOT, CELLS[verb])
            dtype = jnp.dtype(cell.config["dtype"])
            A = jax.eval_shape(lambda a: cell.verb.wrap(a, NB),
                               jax.ShapeDtypeStruct((N, N), dtype))
            B = jax.eval_shape(lambda b: st.from_dense(b, nb=NB),
                               jax.ShapeDtypeStruct((N, NRHS), dtype))
            opts = drive.options(cell.config, False)

            def call(A, B):
                return cell.verb.call(A, B, opts)

            call.__name__ = verb
            text = jax.jit(call).lower(A, B).compile().as_text()
            compiled[verb] = trace.hlo_index(text)[1]
        return compiled[verb]

    return get


def _scopes(names, verb):
    """Scope path of every instruction that carries the program's own
    frame. The rest are its parameters and the bodies of reduce and
    scatter regions, which run inside their parent op."""
    return {ins: trace.scope_of(op) for ins, op in names.items()
            if op.startswith(f"jit({verb})/")}


@pytest.mark.parametrize("verb", sorted(CELLS))
def test_every_op_lands_in_a_phase(program, verb):
    paths = _scopes(program(verb), verb)
    assert paths
    stray = sorted({p for p in paths.values()
                    if phases.layer(p) not in PHASES})
    assert stray == []


@pytest.mark.parametrize("verb", sorted(CELLS))
def test_phase_scopes_sit_outside_the_step_scopes(program, verb):
    seen = set()
    for path in _scopes(program(verb), verb).values():
        parts = path.split("/")
        steps = [p for p in parts if scopes._STEP.match(p)]
        assert all(STEP_NAMES.match(p) for p in steps), path
        if steps:  # a step's ops belong to the step, whatever is inside
            assert phases.layer(path) == scopes.kind(path), path
            assert not any(phases.layer(p) in ("solve", "layout")
                           for p in parts), path
        seen.add(phases.layer(path))
    assert seen == set(PHASES)


def test_gesv_row_swaps_in_panel_trail_and_solve(program):
    """The pivot gathers and scatters are named where they run: in the
    LU panel, in the permuted trailing reads, and in the solve's b[perm];
    posv interchanges no rows."""
    held = {}
    for path in _scopes(program("gesv"), "gesv").values():
        if phases.row_swap(path):
            held.setdefault(phases.layer(path), set()).add(path)
    assert held.get("panel") and held.get("trail")
    assert any(p.startswith("getrs_fwd/") for p in held.get("solve", ()))
    assert not any(phases.row_swap(p)
                   for p in _scopes(program("posv"), "posv").values())
