"""The LUT network's share of its roofline, in %: the least time the chip
could take for the rows answered in the window (``bench/work.py``, the
larger of required operations over peak int8 operations and required
bytes over HBM bandwidth) over the device's busy time in the window.
Every device op of the timed path implements the network, whatever
kernel does it, so the share stays defined when a kernel is replaced."""

from bench import work


def read(ctx):
    """The metric's value, or None when the run has nothing to read."""
    tr = ctx["trace"]
    if not tr or not tr["busy_s"] or not ctx["rows_answered"]:
        return None
    need = work.required_seconds(ctx["cfg"], ctx["rows_answered"],
                                 ctx["batch_rows"], ctx["peaks"])
    return 100.0 * need / tr["busy_s"]
