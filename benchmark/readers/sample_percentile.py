"""A percentile of one of the client's own sample lists, scaled."""
from lib.stats import percentile


def read(ctx, samples, q, scale=1.0):
    xs = ctx.get(samples) or []
    if not xs:
        return None
    return percentile(xs, q) * scale
