"""Device milliseconds of ``_serve_jit`` per serve call: the program's
device time in the traced window over the window's ``serve_batch`` calls
(the benchmark's own span around each call; one call of the server runs
the program once)."""


def read(run):
    calls = len(run.spans.get("serve_batch", ()))
    if run.trace is None or not calls:
        return None
    t = run.trace["program_s"].get("_serve_jit")
    if not t:
        return None
    return 1e3 * t / calls
