"""Real rows over padded rows, over every dispatch of the window's
requests: the share of each fixed-size batch the scheduler filled."""


def read(run):
    if not run.windows:
        return None
    rows = sum(len(w) for w in run.windows)
    return 100.0 * rows / (len(run.windows) * run.batch)
