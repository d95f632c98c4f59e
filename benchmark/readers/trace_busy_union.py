"""From the device trace: busy time (the union of device operations'
intervals, averaged over the chips) as microseconds per pod bound in the
traced part of the window, or the idle share of that part."""


def read(ctx, form):
    tr = ctx["trace"]
    if tr is None or not ctx["trace_window_s"]:
        return None
    if form == "idle_share":
        return 100.0 * (1.0 - tr["busy_s"] / ctx["trace_window_s"])
    if form == "us_per_pod":
        if not ctx["trace_pods_bound"]:
            return None
        return tr["busy_s"] * 1e6 / ctx["trace_pods_bound"]
    raise ValueError(f"trace_busy_union: unknown form {form!r}")
