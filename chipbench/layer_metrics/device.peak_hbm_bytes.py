"""Peak device memory on the fullest chip (bytes), after the window."""


def read(ctx):
    return ctx["counters"]["peak_hbm_bytes"] or None
