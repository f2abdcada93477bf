"""PGs whose up and acting sets were computed and fetched to the host,
per second, from the window's start to the end of its last epoch."""

from benchmark.lib.stats import rate


def read(run):
    return rate(run.window)
