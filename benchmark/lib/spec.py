"""Loading and checking the benchmark's data files.

Everything that belongs to one cell is data: `BENCHMARK.json` names the cell,
its configuration and its traffic mix; `configs/<name>.json`,
`traffic/<name>.json` and `metrics/<name>.json` hold the rest. An unknown key
anywhere is an error, so a typo cannot silently fall back to a default. What a
data file names in code (a pod shape kind, an arrival process, a reference) is
a file too, found by that name (`load_module`).
"""
from __future__ import annotations

import importlib.util
import json
import os
import re
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
BENCH_DIR = os.path.join(ROOT, "benchmark")

NAME_RE = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT_RE = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
SOURCES = ("device_trace", "program_span", "program_counter", "host_clock")


class SpecError(ValueError):
    pass


def check_name(name, what: str) -> str:
    if not isinstance(name, str) or not NAME_RE.match(name):
        raise SpecError(f"{what}: bad name {name!r}")
    return name


def check_unit(unit, what: str) -> str:
    if not isinstance(unit, str) or not UNIT_RE.match(unit):
        raise SpecError(f"{what}: bad unit {unit!r}")
    return unit


def check_keys(obj: dict, required: dict, optional: dict, what: str) -> dict:
    """`obj` holds exactly the required keys and any of the optional ones,
    each of the stated type (a type or tuple of types; None allows null)."""
    if not isinstance(obj, dict):
        raise SpecError(f"{what}: expected an object, got {type(obj).__name__}")
    unknown = set(obj) - set(required) - set(optional)
    if unknown:
        raise SpecError(f"{what}: unknown keys {sorted(unknown)}")
    missing = set(required) - set(obj)
    if missing:
        raise SpecError(f"{what}: missing keys {sorted(missing)}")
    for key, val in obj.items():
        types = required.get(key, optional.get(key))
        if types is None:
            continue
        if not isinstance(types, tuple):
            types = (types,)
        ok = any((t is None and val is None)
                 or (t is not None and isinstance(val, t)
                     and not (t in (int, float) and isinstance(val, bool)))
                 for t in types)
        if not ok:
            raise SpecError(f"{what}.{key}: bad value {val!r}")
    return obj


def load_json(path: str) -> dict:
    with open(path) as f:
        return json.load(f)


def overlaid(base: dict, over: dict | None) -> dict:
    """A deep copy of `base` with `over` laid on it: objects merge key by
    key, anything else replaces. For tools and tests; never written back."""
    out = json.loads(json.dumps(base))
    for key, val in (over or {}).items():
        if isinstance(val, dict) and isinstance(out.get(key), dict):
            out[key] = overlaid(out[key], val)
        else:
            out[key] = val
    return out


def load_module(group: str, name: str, root: str = ROOT):
    """The code a data file names: `benchmark/<group>/<name>.py` of `root`
    (a `-` of the name is a `_` of the file's), imported as `<group>.<name>`.
    A name without its file is a SpecError that says which file was looked
    for, so a new kind is a new file and never an edit here."""
    stem = check_name(name, group).replace("-", "_")
    path = os.path.abspath(os.path.join(root, "benchmark", group, stem + ".py"))
    if not os.path.isfile(path):
        raise SpecError(f"no {group} {name!r}: looked for {path}")
    modname = f"{group}.{stem}"
    mod = sys.modules.get(modname)
    if mod is None or getattr(mod, "__file__", None) != path:
        # by path, not by `sys.path`: a test lays a root of its own over the
        # tree, and the file of that root is the one its data names
        mspec = importlib.util.spec_from_file_location(modname, path)
        mod = importlib.util.module_from_spec(mspec)
        sys.modules[modname] = mod
        try:
            mspec.loader.exec_module(mod)
        except BaseException:
            del sys.modules[modname]
            raise
    return mod


# -- BENCHMARK.json ----------------------------------------------------------
def load_benchmark(root: str = ROOT) -> dict:
    bench = load_json(os.path.join(root, "BENCHMARK.json"))
    check_keys(bench, {"command": list, "paths": list, "run_seconds": int,
                       "configs": list, "workloads": list,
                       "end_to_end": list, "per_layer": list}, {},
               "BENCHMARK.json")
    names = set()
    for c in bench["configs"]:
        check_keys(c, {"name": str, "source": str, "file": str,
                       "reduced": list, "why": str}, {}, "configs[]")
        check_name(c["name"], "config")
    for w in bench["workloads"]:
        check_keys(w, {"name": str, "config": str, "traffic": str,
                       "chips": int, "why": str}, {}, "workloads[]")
        for k in ("name", "config", "traffic"):
            check_name(w[k], f"workload.{k}")
        if w["chips"] not in (1, 4):
            raise SpecError(f"workload {w['name']}: chips must be 1 or 4")
    for m in bench["end_to_end"]:
        check_keys(m, {"name": str, "unit": str, "better": str,
                       "bound": (int, float), "source": str},
                   {"workloads": list}, "end_to_end[]")
    for m in bench["per_layer"]:
        check_keys(m, {"name": str, "unit": str, "better": str,
                       "source": str, "layer": str, "moves": str},
                   {"workloads": list}, "per_layer[]")
    for m in bench["end_to_end"] + bench["per_layer"]:
        check_name(m["name"], "metric")
        check_unit(m["unit"], f"metric {m['name']}")
        if m["better"] not in ("lower", "higher"):
            raise SpecError(f"metric {m['name']}: better={m['better']!r}")
        if m["source"] not in SOURCES:
            raise SpecError(f"metric {m['name']}: source={m['source']!r}")
        if m["name"] in names:
            raise SpecError(f"metric {m['name']} named twice")
        names.add(m["name"])
    return bench


def find_cell(bench: dict, name: str) -> dict:
    for w in bench["workloads"]:
        if w["name"] == name:
            return w
    raise SpecError(f"no workload {name!r} in BENCHMARK.json; it has "
                    f"{[w['name'] for w in bench['workloads']]}")


def metrics_for(bench: dict, cell: dict, group: str) -> list[dict]:
    """The metrics of `group` that this cell reports: those that list it
    under `workloads`, and those without the key whose end-to-end metric the
    cell reports (an end-to-end metric without the key is every cell's)."""
    e2e = [m for m in bench["end_to_end"]
           if "workloads" not in m or cell["name"] in m["workloads"]]
    if group == "end_to_end":
        return e2e
    mine = {m["name"] for m in e2e}
    out = []
    for m in bench["per_layer"]:
        if "workloads" in m:
            if cell["name"] in m["workloads"]:
                out.append(m)
        elif m["moves"] in mine:
            out.append(m)
    return out


# -- configurations ----------------------------------------------------------
_RES = {"cpu_milli": int, "memory_bytes": int}


def load_config(bench: dict, name: str, root: str = ROOT) -> dict:
    entry = next((c for c in bench["configs"] if c["name"] == name), None)
    if entry is None:
        raise SpecError(f"no configuration {name!r} in BENCHMARK.json")
    cfg = load_json(os.path.join(root, entry["file"]))
    check_keys(cfg, {
        "name": str, "source": str, "deployment": str,
        "nodes": dict, "scheduler": dict, "guarantees": dict,
        "reference": str, "check": dict, "store": dict,
        "reduced": list, "assumed": list}, {"resident": (dict, None)},
        f"config {name}")
    if cfg["name"] != name:
        raise SpecError(f"config file of {name!r} names itself {cfg['name']!r}")
    check_keys(cfg["nodes"], {"count": int, "zones": int, "region": str,
                              "allocatable": dict}, {}, "config.nodes")
    check_keys(cfg["nodes"]["allocatable"], {**_RES, "pods": int}, {},
               "config.nodes.allocatable")
    if cfg.get("resident"):
        check_keys(cfg["resident"], {"pods_per_node": int, "services": int,
                                     "requests": dict}, {}, "config.resident")
        check_keys(cfg["resident"]["requests"], _RES, {},
                   "config.resident.requests")
    check_keys(cfg["scheduler"], {"percentage_of_nodes_to_score": int,
                                  "mesh": str, "feature_gates": dict}, {},
               "config.scheduler")
    if cfg["scheduler"]["mesh"] not in ("auto", "none"):
        raise SpecError("config.scheduler.mesh is 'auto' or 'none'")
    check_keys(cfg["check"], {"first_binds": int, "sampled_binds": int}, {},
               "config.check")
    check_keys(cfg["store"], {"watch_log_size": int}, {}, "config.store")
    check_name(cfg["reference"], "config.reference")
    return cfg


# -- traffic -----------------------------------------------------------------
def _named_module(group: str, obj, key: str, what: str, root: str):
    """The module that `obj[key]` names, before `obj`'s other keys are
    checked against what that module states."""
    if not isinstance(obj, dict) or not isinstance(obj.get(key), str):
        raise SpecError(f"{what}: expected an object with a {key}")
    return load_module(group, obj[key], root)


def load_traffic(name: str, root: str = ROOT) -> dict:
    check_name(name, "traffic")
    tr = load_json(os.path.join(root, "benchmark", "traffic", name + ".json"))
    check_keys(tr, {"kind": str, "pod_shapes": list, "why": str},
               {"backlog": int, "arrival": dict, "lifetime_s": (int, float, None),
                "serve": dict, "service_choice": (dict, None),
                "assumed": list, "knee": dict, "warm_binds": int,
                "trace_seconds": (int, float)},
               f"traffic {name}")
    if tr["kind"] not in ("closed_backlog", "open_arrivals"):
        raise SpecError(f"traffic {name}: kind {tr['kind']!r}")
    if not tr["pod_shapes"]:
        raise SpecError(f"traffic {name}: no pod shapes")
    for sh in tr["pod_shapes"]:
        # a kind is `shapes/<kind>.py`, which states the keys of its own
        kind = _named_module("shapes", sh, "kind", "pod_shape", root)
        check_keys(sh, {"kind": str, "share": (int, float), "requests": dict,
                        **kind.REQUIRED},
                   {"labels": dict, **kind.OPTIONAL}, f"pod_shape {sh['kind']}")
        check_keys(sh["requests"], _RES, {}, "pod_shape.requests")
    if abs(sum(sh["share"] for sh in tr["pod_shapes"]) - 1.0) > 1e-9:
        raise SpecError(f"traffic {name}: pod shape shares do not sum to 1")
    if tr.get("service_choice"):
        check_keys(tr["service_choice"], {"policy": str}, {},
                   "traffic.service_choice")
        if tr["service_choice"]["policy"] != "per-cycle":
            raise SpecError("service_choice.policy is 'per-cycle'")
    if tr["kind"] == "closed_backlog":
        if "backlog" not in tr or tr["backlog"] < 1:
            raise SpecError(f"traffic {name}: closed_backlog needs backlog >= 1")
        if "arrival" in tr or "serve" in tr:
            raise SpecError(f"traffic {name}: closed_backlog takes no arrival/serve")
    else:
        for k in ("arrival", "serve", "lifetime_s"):
            if tr.get(k) is None:
                raise SpecError(f"traffic {name}: open_arrivals needs {k}")
        if tr.get("service_choice"):
            raise SpecError(f"traffic {name}: per-cycle service choice needs "
                            f"the cycles of a closed_backlog")
        # a process is `arrivals/<process>.py`; `rate_per_s` is every
        # process's mean rate (the admission gate is sized from it)
        process = _named_module("arrivals", tr["arrival"], "process",
                                "traffic.arrival", root)
        check_keys(tr["arrival"], {"process": str, "rate_per_s": (int, float),
                                   **process.REQUIRED}, process.OPTIONAL,
                   f"traffic.arrival {tr['arrival']['process']}")
        check_keys(tr["serve"], {"window_size": int, "depth": int,
                                 "gate_seconds": (int, float),
                                 "retry_after_base_s": (int, float),
                                 "give_up_after": int,
                                 "settle_timeout_s": (int, float)}, {},
                   "traffic.serve")
    return tr


# -- per-layer metric files --------------------------------------------------
def load_metric(name: str, root: str = ROOT) -> dict:
    check_name(name, "metric")
    m = load_json(os.path.join(root, "benchmark", "metrics", name + ".json"))
    check_keys(m, {"reader": str, "args": dict, "what": str}, {},
               f"metric file {name}")
    check_name(m["reader"], "metric.reader")
    return m


def load_peaks(device_kind: str, root: str = ROOT) -> dict:
    peaks = load_json(os.path.join(root, "benchmark", "peaks.json"))
    if device_kind not in peaks["devices"]:
        raise SpecError(f"device kind {device_kind!r} is not in "
                        f"benchmark/peaks.json; it lists "
                        f"{sorted(peaks['devices'])}")
    return peaks["devices"][device_kind]
