"""Mutation step: mean device time of one insert, delete or update program
launch, from the ``XLA Modules`` line of the device trace, in ms."""

MUTATION = r"^jit__(insert|delete|update)$"


def read(ctx):
    runs = ctx.device.launches(MUTATION)
    return sum(runs) / len(runs) * 1e3 if runs else None
