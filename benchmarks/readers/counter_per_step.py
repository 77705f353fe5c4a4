"""A program counter's rise over the window, per step."""


def read(run, spec):
    if spec["counter"] not in run["counters1"]:
        return None
    return ((run["counters1"][spec["counter"]]
             - run["counters0"].get(spec["counter"], 0))
            / len(run["spans"]))
