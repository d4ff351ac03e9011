"""Device busy time in the traced window per traversal sweep run in it (ms)."""


def read(ctx):
    red = ctx.get("reduced")
    sweeps = ctx["stats"].get("sweeps", 0)
    if red is None or sweeps <= 0:
        return None
    return 1000.0 * red["busy_s"] / sweeps
