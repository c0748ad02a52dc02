"""Edges trained per second: every step's edges over the window's wall
time (whole bursts, reset passes included)."""


def read(run):
    edges = run.counts.get("edges")
    if not edges or not run.window_s:
        return None
    return edges / run.window_s
