"""Device-busy time (union of the device's operation intervals in the trace)
per traced optimizer step."""

UNIT = "ms"


def read(run):
    if not run["trace"] or not run["traced"]["steps"]:
        return None
    return 1e3 * run["trace"]["busy_s"] / run["traced"]["steps"]
