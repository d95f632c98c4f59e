"""From the device trace: time of the leaf operations whose name stack lies
under one of the kernels' named scopes (`filter`, `score`, `pick`, `fold`;
`lib/spans.py` looks the scope up in the HLO the profiler kept), averaged
over the chips, as microseconds per pod bound in the traced part. Nothing
when no traced program carries a scope (an older commit)."""
from lib import spans as sp


def read(ctx, scope):
    if ctx["trace"] is None or not ctx["trace_pods_bound"]:
        return None
    path = sp.find_xplane()
    if path is None:
        return None
    got = sp.load(path)
    if not got["scoped_programs"]:
        return None
    return got["scope_ns"][scope] / 1e3 / ctx["trace_pods_bound"]
