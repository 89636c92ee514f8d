"""Share of the traced window in which no operation ran on the device (%),
averaged over the chips used."""


def read(ctx):
    return ctx["trace"] and 100.0 * ctx["trace"]["idle_share"]
