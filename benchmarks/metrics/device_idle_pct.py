"""Share of the traced block in which no operation ran on the device."""


def read(ctx):
    tr = ctx.get("trace")
    if not tr or not tr["window_s"]:
        return None
    return 100.0 * (1.0 - tr["busy_s"] / tr["window_s"])
