"""Share of the traced window in which no operation ran on the device,
in %: 1 - (union of device op intervals) / window."""


def read(ctx):
    """The metric's value, or None when the run has nothing to read."""
    tr = ctx["trace"]
    if not tr or not tr["window_s"] or not tr["busy_s"]:
        return None
    return 100.0 * (1.0 - tr["busy_s"] / tr["window_s"])
