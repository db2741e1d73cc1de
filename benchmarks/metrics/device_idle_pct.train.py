"""1 - busy / window of the traced part of a training window."""

UNIT = "%"


def read(run):
    if not run["trace"]:
        return None
    t = run["trace"]
    return 100.0 * (1.0 - t["busy_s"] / t["window_s"])
