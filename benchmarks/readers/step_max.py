"""Longest step of the window, on the harness's clock."""


def read(run, spec):
    return max(e - s for s, e in run["spans"])
