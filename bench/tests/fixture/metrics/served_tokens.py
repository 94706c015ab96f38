"""Fixture reader: a metric added as one file, found by its name."""


def read(run):
    return sum(len(s.times) for s in run.window.served)
