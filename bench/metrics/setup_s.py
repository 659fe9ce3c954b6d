"""setup_s: seconds from process start to the window's start (loading,
weights, warm-up, compilation or cache loads), on the host clock."""


def read(run):
    return run.host["setup_s"]
