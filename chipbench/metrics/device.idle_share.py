"""Device: share of the traced window in which no program ran on the
chip (1 - busy / window), in percent."""


def read(ctx):
    if ctx.dev is None or ctx.dev["window_s"] <= 0:
        return None
    return 100.0 * (1.0 - ctx.dev["busy_s"] / ctx.dev["window_s"])
