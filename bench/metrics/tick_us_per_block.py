"""Host seconds in ``LUTFleet.tick`` and ``LUTFleet.drain`` per block
dispatched (``FleetStats.ticks``), in us: the fleet scheduler and the
engine's dispatch and retire."""


def read(ctx):
    """The metric's value, or None when the run has nothing to read."""
    fleet = ctx["fleet"]
    if not fleet or not fleet["ticks"]:
        return None
    secs = sum(ctx["spans"].get(k, (0.0, 0))[0] for k in ("tick", "drain"))
    return secs / fleet["ticks"] * 1e6 if secs else None
