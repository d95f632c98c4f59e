"""From the profiler trace, by the program's own spans (`lib/spans.py`).

`wall_share`: self time of the named spans (a span's duration minus what the
spans nested in it cover) as a share of the traced seconds. `spans` holds
names; one that ends in `.` is a prefix (`pump.` is every informer's pump).

`idle_unnamed`: the share of chip 0's idle time that no span of the program
and no `client.*` span covers: what is left to the benchmark's own wrappers
(`sched.*`, `loop.*`) or to nothing. A gap goes to the innermost span over
it. Nothing when the program opened no span of its own (an older commit)."""
from lib import spans as sp
from lib import trace as tr


def read(ctx, form, spans=()):
    if ctx["trace"] is None or not ctx["trace_window_s"]:
        return None
    path = sp.find_xplane()
    if path is None:
        return None
    got = sp.load(path)
    segments = got["segments"]
    program = [n for n in got["spans"]
               if not n.startswith(("client.",) + sp.WRAPPERS)]
    if not program:
        return None
    if form == "wall_share":
        self_ns = sum(e - s for s, e, name in segments
                      if sp.matches(name, spans))
        return 100.0 * self_ns / (ctx["trace_window_s"] * 1e9)
    if form == "idle_unnamed":
        gaps = tr.name_gaps(got["busy0"], segments)
        idle = sum(gaps.values())
        if idle <= 0:
            return None
        unnamed = sum(ns for name, ns in gaps.items()
                      if name == tr.NO_SPAN or name.startswith(sp.WRAPPERS))
        return 100.0 * unnamed / idle
    raise ValueError(f"trace_span_share: unknown form {form!r}")
