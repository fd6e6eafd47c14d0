"""The whole step's share of the chip's peak, in %: required operations
of the rows answered in the window (``bench/work.py``) over the window
times the peak int8 operation rate."""

from bench import work


def read(ctx):
    """The metric's value, or None when the run has nothing to read."""
    if not ctx["rows_answered"] or not ctx["window_s"]:
        return None
    ops, _ = work.required(ctx["cfg"], ctx["rows_answered"],
                           ctx["batch_rows"])
    return 100.0 * ops / (ctx["window_s"] * ctx["peaks"]["int8_ops_per_s"])
