"""Lane sweeps spent per lane seeded in the window (``ServeStats``:
``lane_sweeps_busy / lanes_used``); early exit shows here."""


def read(ctx):
    used = ctx["stats"].get("lanes_used", 0)
    if used <= 0:
        return None
    return ctx["stats"]["lane_sweeps_busy"] / used
