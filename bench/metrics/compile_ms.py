"""Host time per loop call of making ops' programs: each op's first call,
which traces, lowers and compiles (or loads from the persistent cache) its
jitted program, then dispatches it (the program's `op.compile` span). 0.0
where the window's calls only dispatch programs made before it."""
from bench import spans


def read(ctx):
    tr = ctx["trace"]
    made = spans.in_window(tr, "op.compile")
    if made:
        return spans.total_ms(made) / ctx["units"]
    return 0.0 if spans.in_window(tr, "op.dispatch") else None
