"""setup_s: seconds from the process start to the window's opening
(loading, weights, warm-up, and in a cold run compiling), host clock."""


def read(run):
    return run["setup_s"]
