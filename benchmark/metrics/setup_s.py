"""Process start to the first timed call (host clock): imports, the
pool made from the seed, and the warm-up call over every pool batch."""


def read(run):
    return run.setup_s
