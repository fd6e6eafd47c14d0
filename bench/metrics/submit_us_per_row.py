"""Host seconds in ``LUTFleet.submit_many`` per admitted row, in us
(admission and request creation: ``serve/fleet.py``,
``serve/lut_engine.py``)."""


def read(ctx):
    """The metric's value, or None when the run has nothing to read."""
    secs, _ = ctx["spans"].get("submit", (0.0, 0))
    rows = ctx["rows_admitted"]
    return secs / rows * 1e6 if rows and secs else None
