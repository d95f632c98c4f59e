"""A number the harness itself took during the run."""


def read(ctx, key):
    v = ctx.get(key)
    return None if v is None else float(v)
