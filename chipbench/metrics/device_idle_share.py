"""Share of the traced stretch in which no operation ran on the device."""


def read(ctx):
    if ctx["window_s"] <= 0 or not ctx["ops"] or not ctx["segments"]:
        return None
    return 100.0 * (1.0 - ctx["busy_s"] / ctx["window_s"])
