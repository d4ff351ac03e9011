"""Busy share of the lane slots swept in the window (%, ``ServeStats``:
``lane_sweeps_busy / lane_sweeps_total``)."""


def read(ctx):
    total = ctx["stats"].get("lane_sweeps_total", 0)
    if total <= 0:
        return None
    return 100.0 * ctx["stats"]["lane_sweeps_busy"] / total
