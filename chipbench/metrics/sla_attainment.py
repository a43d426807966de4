"""Share of the requests sent in the window whose end-to-end time met
their own SLA.  A request shed or failed counts as a miss."""


def read(run):
    return float(run.requests["met"].mean())
