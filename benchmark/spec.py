"""Everything the harness knows about a cell, found by name.

  BENCHMARK.json                 the cells and metrics (checkout root)
  benchmark/configs/<c>.json     a configuration: physics keys, source
  benchmark/references/<c>.py    the plain reference of a configuration
  benchmark/traffic/<t>.json     a mix: grid, solve budget, mesh, segment
  benchmark/limits/<cell>.json   the correctness limits of one cell
  benchmark/metrics/<m>.py       one reader per per-layer metric
  benchmark/layers/<l>.json      one file of attribution rules per layer
  benchmark/peaks.json           device peaks by device_kind

A later change adds a cell, a metric or a layer by adding files; nothing
here lists them.
"""

from __future__ import annotations

import importlib.util
import json
import os

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)


def load_json(path: str):
    with open(path) as fh:
        return json.load(fh)


def benchmark(root: str = ROOT) -> dict:
    return load_json(os.path.join(root, "BENCHMARK.json"))


def _named(kind: str, name: str, ext: str = ".json", here: str = HERE) -> str:
    path = os.path.join(here, kind, name + ext)
    if not os.path.exists(path):
        raise FileNotFoundError(f"no {kind} file for {name!r} ({path})")
    return path


def _module(kind: str, name: str, here: str = HERE):
    """The module in `<here>/<kind>/<name>.py`, loaded by its path."""
    path = _named(kind, name, ext=".py", here=here)
    spec = importlib.util.spec_from_file_location(
        f"bench_{kind}_{name.replace('.', '_').replace('-', '_')}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def cell(name: str, bench: dict | None = None, here: str = HERE) -> dict:
    """The workload entry of a cell, with its configuration, traffic and
    limits loaded, `par`: the .par keys of the run (configuration keys,
    then the traffic's), and `reference`: the configuration's reference
    module (`check_covered`, `field_names`, `initial_fields`,
    `run_segment`, and optionally `compared`)."""
    bench = benchmark() if bench is None else bench
    entries = [w for w in bench["workloads"] if w["name"] == name]
    if not entries:
        raise KeyError(f"no workload named {name!r} in BENCHMARK.json")
    w = dict(entries[0])
    cfg_entry = next(c for c in bench["configs"] if c["name"] == w["config"])
    w["config_file"] = load_json(os.path.join(os.path.dirname(here),
                                              cfg_entry["file"]))
    w["traffic_file"] = load_json(_named("traffic", w["traffic"], here=here))
    w["limits"] = load_json(_named("limits", name, here=here))
    w["reference"] = _module("references", w["config"], here=here)
    par = dict(w["config_file"]["par"])
    par.update(w["traffic_file"]["par"])
    w["par"] = par
    return w


def metrics_for(bench: dict, cell_name: str, section: str) -> list:
    """The metrics of `section` ("end_to_end" or "per_layer") that this
    cell reports: those without a `workloads` key, and those that list
    it."""
    return [m for m in bench[section]
            if "workloads" not in m or cell_name in m["workloads"]]


def reader(metric: str, here: str = HERE):
    """The `read(ctx)` function of a per-layer metric's own file."""
    return _module("metrics", metric, here=here).read


def layer_rules(here: str = HERE) -> list:
    """Every layer's attribution rules, in file-name order: a device op
    belongs to the first layer whose pattern matches it."""
    out = []
    d = os.path.join(here, "layers")
    for fn in sorted(os.listdir(d)):
        if fn.endswith(".json"):
            rules = load_json(os.path.join(d, fn))
            rules["key"] = fn[:-5]
            out.append(rules)
    return out


def peaks(device_kind: str, here: str = HERE) -> dict:
    """Published peaks of a device; an unknown device is an error."""
    table = load_json(os.path.join(here, "peaks.json"))["devices"]
    if device_kind not in table:
        raise KeyError(f"device_kind {device_kind!r} is not in "
                       "benchmark/peaks.json; add its published peaks")
    return table[device_kind]
