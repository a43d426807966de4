"""Model layer: tokens the pool served in the traced window (each
``RequestResult.tokens_served``: prompt's next token plus each decode
step's, 0 when shed) per second the device was busy there.  ``execute``
runs only in the window, so the executor's results are the window's.
Nothing where the program does not count served tokens."""


def read(run):
    if run.trace is None or run.trace.busy_s <= 0:
        return None
    counts = [getattr(r, "tokens_served", None) for r in run.ex.results]
    if not counts or None in counts or not sum(counts):
        return None
    return sum(counts) / run.trace.busy_s
