"""Share of the dispatched block rows that were padding, in %, from
``FleetStats.rows_padded`` over ``ticks * block`` (engine batching)."""


def read(ctx):
    """The metric's value, or None when the run has nothing to read."""
    fleet = ctx["fleet"]
    if not fleet or not fleet["ticks"]:
        return None
    return 100.0 * fleet["rows_padded"] / (fleet["ticks"] * fleet["block"])
