"""Share of the traced window in which no operation ran on the device."""


def read(result, summary, ctx):
    return 100.0 * (1.0 - summary["busy_s"] / summary["window_s"])
