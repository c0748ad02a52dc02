"""Median host time of one ``SwapServer.serve_batch`` call in the window
(the benchmark's own span around the call)."""
import numpy as np


def read(run):
    v = run.spans.get("serve_batch")
    if not v:
        return None
    return float(np.median(v)) * 1e3
