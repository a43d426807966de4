"""Requests routed (admitted or shed) per second of the whole window."""


def read(run):
    return run.rows_routed / run.window_s
