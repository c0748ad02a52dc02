"""Seconds the whole benchmark process stood still in the window: the sum
of every stall over 20 ms that the serve cell's watchdog thread saw (a
5 ms sleep that woke that late).  No thread could run Python then, so
requests waited without the device being given work."""
import numpy as np


def read(run):
    v = run.samples.get("process_stall_s")
    if v is None:
        return None
    return float(np.sum(v))
