"""Set-up seconds: from process start to the window's start (loading,
data and weights from the seed, warm-up and compilation)."""


def read(run):
    return run.setup_s
