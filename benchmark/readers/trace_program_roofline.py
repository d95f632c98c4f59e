"""A jitted program's share of its HBM roofline, from the device trace.

Least time = bytes the program must move per launch x launches / the
device's peak HBM bandwidth (`peaks.json`); the share is that over the
program's device time on the `XLA Modules` line. The bytes come from the
function `model` of `roofline/<module>.py`, called with the launch's shapes:
`rows` (node rows padded to a power of two and split over the chips), `pods`
(pods bound in the traced part / launches) and `nodes` (the cluster's). A new
program's byte model is a new file there and the metric file that names it."""
import importlib

from roofline.bytes import pad_pow2


def read(ctx, program, model, module="bytes"):
    tr = ctx["trace"]
    if tr is None or ctx["peaks"] is None:
        return None
    mod = tr["modules"].get(program)
    if not mod or not mod["seconds"] or not mod["launches"]:
        return None
    nodes = ctx["cfg"]["nodes"]["count"]
    per_launch = getattr(importlib.import_module(f"roofline.{module}"), model)(
        rows=pad_pow2(nodes) // max(1, tr["devices"]),
        pods=int(ctx["trace_pods_bound"] / mod["launches"]), nodes=nodes)
    least_s = per_launch * mod["launches"] / ctx["peaks"]["hbm_bytes_per_s"]
    return 100.0 * least_s / mod["seconds"]
