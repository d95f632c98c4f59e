"""`trace_scope_time` for a named scope that `lib/spans.py`'s `SCOPES` does
not list (`rotate`, which `ops/kernels.py` nests in `filter` and `pick`):
device time of the leaf operations whose innermost named scope is `scope`,
averaged over the chips, as microseconds per pod bound in the traced part.
Nothing when no traced program carries a scope (an older commit); 0 when
none carries this one.

`lib/spans.py` looks scopes up by its own fixed tuple, and only a `benchmark`
PR may edit that file, so this reader runs that module's `load` once more
with `scope` added to the tuple: the same parse, the same leaf rule, the same
vote on a fusion that has no name stack of its own. The readers of the listed
scopes do not see the added one, so `filter/rotate/gather` stays under
`filter` for them. It costs a second parse of the trace."""
from lib import spans as sp


def read(ctx, scope):
    if ctx["trace"] is None or not ctx["trace_pods_bound"]:
        return None
    path = sp.find_xplane()
    if path is None:
        return None
    listed = sp.SCOPES
    sp.SCOPES = (scope,) + tuple(s for s in listed if s != scope)
    try:
        got = sp.load.__wrapped__(path)
    finally:
        sp.SCOPES = listed
    if not got["scoped_programs"]:
        return None
    return got["scope_ns"][scope] / 1e3 / ctx["trace_pods_bound"]
