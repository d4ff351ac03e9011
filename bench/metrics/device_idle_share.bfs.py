"""Share of the traced window in which no operation ran on the device (%)."""


def read(ctx):
    red = ctx.get("reduced")
    if red is None:
        return None
    return 100.0 * red["idle_share"]
