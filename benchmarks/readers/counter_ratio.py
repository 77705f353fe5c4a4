"""The rise of one program counter over the window against the rise of
another, times `scale`: a share of slots, jobs a dispatch.  A program
without the counters, or a window in which the second did not rise,
gives nothing to read."""


def read(run, spec):
    def rise(name):
        return run["counters1"].get(name, 0) - run["counters0"].get(name, 0)

    if spec["counter"] not in run["counters1"] or not rise(spec["over"]):
        return None
    return spec["scale"] * rise(spec["counter"]) / rise(spec["over"])
