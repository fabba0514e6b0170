"""Process start to the first timed step: loading, weights, compilation
(from the persistent cache after a cell's first run) and warm-up."""


def read(run):
    return run.setup_s
