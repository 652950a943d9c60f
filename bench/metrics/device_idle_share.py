"""Share of the traced window, in percent, in which no operation ran on
the device: 1 − (union of the device's operation intervals ÷ window),
averaged over the chips used."""


def read(ctx):
    red = ctx["reduction"]
    if not red.devices or red.window_s <= 0:
        return None
    return 100.0 * (1.0 - red.busy_s / red.window_s)
