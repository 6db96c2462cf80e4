"""Which layer of the drivers a device op belongs to, by its scope path.

``linalg/cholesky.py`` and ``linalg/lu.py`` name each step of their
outer loop with ``jax.named_scope``: ``potrf_l<k>_tile``,
``potrf_l<k>_panel``, ``getrf_l<k>_panel`` (each maybe ``_lookahead``)
for the panel chain, and ``<verb>_l<k>_trail``, ``_trail_next``,
``_trail_rest`` for the trailing updates. A path can hold several of
them; the innermost decides. Ops under none of them (a nested jitted
helper keeps only its own frames) are counted apart as unscoped.
"""

from __future__ import annotations

import re

_STEP = re.compile(r"^[a-z]+_l\d+_(tile|panel|trail)")
KIND = {"tile": "panel", "panel": "panel", "trail": "trail"}


def kind(scope: str) -> str:
    """'panel', 'trail' or 'unscoped'."""
    for part in reversed(scope.split("/")):
        m = _STEP.match(part)
        if m:
            return KIND[m.group(1)]
    return "unscoped"


def seconds_by_kind(scope_s: dict) -> dict:
    out = {"panel": 0.0, "trail": 0.0, "unscoped": 0.0}
    for scope, s in scope_s.items():
        out[kind(scope)] += s
    return out


def per_call_ms(ctx, which: str):
    """Device ms per call of the verb's program in ``which`` kind of
    scope; None where the program did not run in the traced window."""
    tr = ctx["trace"]
    runs = tr.modules.get(ctx["program"]) if tr else None
    if not runs:
        return None
    return 1e3 * seconds_by_kind(tr.scope_s)[which] / len(runs)
