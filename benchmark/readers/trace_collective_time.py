"""From the device trace: time in collective operations (all-gather,
all-reduce, collective-permute, ...), averaged over the chips, as
microseconds per pod bound in the traced part of the window."""


def read(ctx):
    tr = ctx["trace"]
    if tr is None or not ctx["trace_pods_bound"] or ctx["n_devices"] < 2:
        return None
    return tr["collective_s"] * 1e6 / ctx["trace_pods_bound"]
