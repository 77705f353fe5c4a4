"""Share of the traced steps in which no operation ran on the device."""


def read(run, spec):
    trace = run.get("trace")
    if trace is None or not trace["window_s"]:
        return None
    return 100.0 * (1.0 - trace["busy_s"] / trace["window_s"])
