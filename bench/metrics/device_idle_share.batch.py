"""Share of the traced window in which no operation ran on the device."""


def read(ctx):
    s = ctx["summary"]
    return 1.0 - s["busy_s"] / s["window_s"] if s["devices"] else None
