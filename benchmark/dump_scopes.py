#!/usr/bin/env python3
"""Trace a few replays of a cell and reduce them by the program's own
spans and scopes (benchmark/scopes.py), beside today's reduction.

  python3 benchmark/dump_scopes.py --workload <cell> --seed <n> --out <dir>

Writes <dir>/planes.txt (as benchmark/dump_trace.py does), <dir>/trace.json.gz
(the trace as benchmark/tracered.load keeps it), <dir>/scopes.json (the
chunk program's op scopes from its compiled HLO text, a compile-cache hit)
and <dir>/result.json: the four scope numbers, the per-layer numbers of
today's layer rules, both namings of the idle gaps, the replay walls with
and without an active profiler, and the cost of one `profiling.region`
with and without it. Runs on the chip only.
"""

from __future__ import annotations

import argparse
import gzip
import json
import os
import shutil
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, HERE)

import scopes  # noqa: E402
import tracered  # noqa: E402


def compiled_text(solver) -> str:
    """The optimized HLO text of the chunk program that runs."""
    chunk = getattr(solver, "_chunk_sm", None) or solver._chunk_fn
    return chunk.lower(*solver.initial_state()).compile().as_text()


def coverage(trace: dict, table: dict) -> float | None:
    """Share of the chunk program's device op time, inside the replays,
    spent in ops the HLO text names: 1.0 when the text is that of the
    program that ran."""
    replay = tracered.merge([[s, s + d] for n, s, d in trace["host"]
                             if n == tracered.REPLAY])
    names = set(table["names"])
    seen = total = 0.0
    for dev in trace["devices"]:
        execs = [(s, s + d) for n, s, d in dev["modules"]
                 if scopes.module_base(n) == table["module"]]
        for n, s, d, _lab in dev["ops"]:
            if d > 0 and any(a <= s < b for a, b in execs) and \
                    tracered.clip([[s, s + d]], replay):
                total += d
                seen += d if n in names else 0
    return seen / total if total else None


def replays(harness, solver, n: int, ann):
    walls, steps = [], 0
    for _ in range(n):
        with ann("bench.reset"):
            harness.reset(solver)
        with ann("bench.replay"):
            t0 = time.perf_counter()
            steps += harness.replay(solver)
            walls.append(time.perf_counter() - t0)
    return walls, steps


def region_cost_us(calls: int = 3000) -> float:
    """Microseconds per `profiling.region` enter and exit."""
    from pampi_tpu.utils import profiling

    t0 = time.perf_counter()
    for _ in range(calls):
        with profiling.region("drive.wait"):
            pass
    return 1e6 * (time.perf_counter() - t0) / calls


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, default=1)
    ap.add_argument("--replays", type=int, default=2)
    ap.add_argument("--out", required=True)
    args = ap.parse_args(argv)
    import run

    run.environment()
    import contextlib

    import jax

    import dump_trace
    import harness
    import spec

    cell = spec.cell(args.workload)
    devs = run.devices_for(cell["chips"])
    solver = harness.setup(cell, args.seed)
    table = scopes.op_scopes(compiled_text(solver))
    plain = lambda _name: contextlib.nullcontext()  # noqa: E731
    walls_off, _ = replays(harness, solver, args.replays, plain)
    cost_off = region_cost_us()
    shutil.rmtree(run.TRACE_DIR, ignore_errors=True)
    os.makedirs(run.TRACE_DIR)
    jax.profiler.start_trace(run.TRACE_DIR)
    walls_on, steps = replays(harness, solver, args.replays,
                              jax.profiler.TraceAnnotation)
    cost_on = region_cost_us()
    jax.profiler.stop_trace()
    xp = tracered.xplane_file(run.TRACE_DIR)
    os.makedirs(args.out, exist_ok=True)
    with open(os.path.join(args.out, "planes.txt"), "w") as fh:
        dump_trace.describe(xp, fh)
    trace = tracered.load(xp)
    shutil.rmtree(run.TRACE_DIR, ignore_errors=True)
    with gzip.open(os.path.join(args.out, "trace.json.gz"), "wt") as fh:
        json.dump(trace, fh)
    with open(os.path.join(args.out, "scopes.json"), "w") as fh:
        json.dump(table, fh)
    red = tracered.reduce(trace, spec.layer_rules())
    try:
        peak = spec.peaks(devs[0].device_kind)
    except KeyError:  # a device without published peaks reads no roofline
        peak = None
    ctx = tracered.Context(red, steps, cell["par"], peak, None, cell["chips"])
    rules = {}
    for m in spec.benchmark()["per_layer"]:
        if m["name"] != "compile_s" and (
                "workloads" not in m or cell["name"] in m["workloads"]):
            rules[m["name"]] = spec.reader(m["name"])(ctx)
    result = {
        "workload": cell["name"], "steps": steps,
        "chunks": sum(1 for n, _s, _d in trace["host"]
                      if n == "drive.dispatch"),
        "scopes": scopes.metrics(trace, table, steps),
        "rules": rules,
        "scoped_ops": len(table["ops"]), "module": table["module"],
        "coverage": coverage(trace, table),
        "idle_gaps": red["idle_gaps"],
        "idle_gaps_by_span": scopes.idle_gaps(trace),
        "replay_wall_s": {"profiler_off": walls_off,
                          "profiler_on": walls_on},
        "region_us": {"profiler_off": cost_off, "profiler_on": cost_on},
        "reduction": scopes.reduce(trace, table),
    }
    with open(os.path.join(args.out, "result.json"), "w") as fh:
        json.dump(result, fh, indent=1)
    print(json.dumps({k: result[k] for k in (
        "workload", "steps", "chunks", "scopes", "rules", "scoped_ops",
        "coverage",
        "idle_gaps_by_span", "replay_wall_s", "region_us")}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
