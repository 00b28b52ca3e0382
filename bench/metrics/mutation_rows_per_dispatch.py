"""Runtime: mutation rows acked inside the profile over the launches of
mutation programs in its device trace."""

MUTATION = r"^jit__(insert|delete|update)$"


def read(ctx):
    launches = len(ctx.device.launches(MUTATION))
    return ctx.rows_acked_traced / launches if launches else None
