"""Which phase of a verb's program a device op belongs to, by its scope
path, and the device time per call in each.

The step scopes of ``benchmark/scopes.py`` come first: an op under a
``<verb>_l<k>_tile``, ``_panel`` or ``_trail*`` scope is ``panel`` or
``trail`` as there. The drivers and solves name the rest:

- ``solve``: the triangular sweeps, ``potrs_fwd``/``potrs_bwd``
  (``linalg/cholesky.py``) and ``getrs_fwd``/``getrs_bwd``
  (``linalg/lu.py``), each with its operand's canonicalization, pad and
  row permute;
- ``layout``: the drivers' ``potrf_prologue``/``getrf_prologue``
  (canonicalize, pad the diagonal), ``potrf_epilogue``/
  ``getrf_epilogue`` (``tril``, the deferred left swaps,
  ``from_dense``), and, between the step scopes, each step's
  ``<verb>_l<k>_store`` (the writes of its tile, panel and trailing
  blocks, its pivot and info bookkeeping) and ``getrf_l<k>_load`` (the
  trailing block and its pivot rows, read once for both halves of a
  lookahead step's update).

An op in none of them is ``unscoped``: above all the copies and
fusions that XLA inserts with no ``op_name``, which no scope reaches,
and the few ops of a nested jitted helper whose ``op_name`` XLA leaves
without its caller's frames.
``row_swap`` (``ops/blocked.py``, ``linalg/lu.py``) marks every pivot
gather and scatter inside whichever of these holds it, so its time is
a subset across phases, as the Pallas kernels are a subset of the
panel chain.
"""

from __future__ import annotations

import re

from benchmark import scopes

_SOLVE = re.compile(r"^(potrs|getrs)_")
_LAYOUT = re.compile(r"_(prologue|epilogue)$|^[a-z]+_l\d+_(load|store)$")
ROW_SWAP = "row_swap"
LAYERS = ("panel", "trail", "solve", "layout", "unscoped")


def layer(scope: str) -> str:
    """One of ``LAYERS`` for a scope path (``trace.scope_of``)."""
    step = scopes.kind(scope)
    if step != "unscoped":
        return step
    parts = scope.split("/")
    if any(_SOLVE.match(p) for p in parts):
        return "solve"
    if any(_LAYOUT.search(p) for p in parts):
        return "layout"
    return "unscoped"


def row_swap(scope: str) -> bool:
    return ROW_SWAP in scope.split("/")


def _per_call(ctx, pick):
    """Device ms per call of the verb's program in the scopes ``pick``
    takes; None where the program did not run in the traced window or
    no op of its falls there (a program without these scopes)."""
    tr = ctx["trace"]
    runs = tr.modules.get(ctx["program"]) if tr else None
    if not runs:
        return None
    picked = [s for scope, s in tr.scope_s.items() if pick(scope)]
    if not picked:
        return None
    return 1e3 * sum(picked) / len(runs)


def layer_ms(ctx, which: str):
    """Device ms per call in the phase ``which``."""
    return _per_call(ctx, lambda scope: layer(scope) == which)


def row_swap_ms(ctx):
    """Device ms per call under a ``row_swap`` scope, in any phase."""
    return _per_call(ctx, row_swap)
