"""A program counter's rise over the whole window (a count that should
read 0, such as compiles, is reported as it is)."""


def read(run, spec):
    return (run["counters1"].get(spec["counter"], 0)
            - run["counters0"].get(spec["counter"], 0))
