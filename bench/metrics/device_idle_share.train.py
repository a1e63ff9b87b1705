"""Share of the traced training unit in which no operation ran on the
device, %: 1 - busy / window, from the profiler trace."""


def read(rec):
    tr = rec.get("trace")
    if not tr or tr["window_s"] <= 0:
        return None
    return 100.0 * (1.0 - tr["busy_s"] / tr["window_s"])
