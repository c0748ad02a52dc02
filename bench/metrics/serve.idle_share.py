"""Device idle share of the traced window: one minus the union of
device operation intervals over the window, in percent."""


def read(run):
    if run.trace is None or run.trace.get("idle_share") is None:
        return None
    return 100.0 * run.trace["idle_share"]
