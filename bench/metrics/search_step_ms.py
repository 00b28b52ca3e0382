"""Search step: mean device time of one search-program launch, from the
``XLA Modules`` line of the device trace, in ms."""

SEARCH = r"^jit__search$"


def read(ctx):
    runs = ctx.device.launches(SEARCH)
    return sum(runs) / len(runs) * 1e3 if runs else None
