"""Share of the traced window in which no operation ran on the device, in
percent, averaged over the chips the cell uses."""


def read(ctx):
    tr = ctx["trace"]
    if tr["window_s"] <= 0 or not tr["devices"]:
        return None
    return (1.0 - tr["busy_s"] / tr["window_s"]) * 100.0
