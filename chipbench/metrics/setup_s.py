"""Process start to the window opening: weights, compile or cache load,
warm-up."""


def read(run):
    return run.setup_s
