"""A program counter's value when set-up ended (the process starts at
zero, so this is what set-up added)."""


def read(run, spec):
    return run["counters0"].get(spec["counter"])
