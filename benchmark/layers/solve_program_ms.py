"""Device time per execution of the Session's resident solve program,
``serve_<factor>_solve`` (``runtime/session.py``), over the traced
window of served requests, in ms; nothing where that program did not
run."""


def read(ctx):
    tr = ctx["trace"]
    runs = tr.modules.get(f"jit_serve_{ctx['factor']}_solve") if tr else None
    if not runs:
        return None
    return 1e3 * sum(runs) / len(runs)
