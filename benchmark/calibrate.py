#!/usr/bin/env python3
"""The readings a cell's correctness limits are set from, in one process.

  python3 benchmark/calibrate.py --workload <cell> --seeds 1,2,... \
      [--control-seeds 1,2,3] [--out <file.json>]

For each seed: the program's replay of the segment from that seed's
initial fields (the same solver object, built once through the CLI, as
in a timed run) against the float32 reference. For each control seed:
the control, the reference itself computed with bfloat16 storage (the
nearest precision below the float32 the configuration states), against
the float32 reference. A limit lies above every program reading and
below every control reading (benchmark/limits/<cell>.json records both).
The benchmark's own runs never run the control.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, HERE)


def control_readings(cell: dict, seed: int, ref=None) -> dict:
    """The control's readings for one seed: bfloat16 reference against
    the float32 one."""
    import harness

    if ref is None:
        ref = harness.reference_fields(cell, seed, "float32")
    ctrl, ctrl_steps = harness.reference_fields(cell, seed, "bfloat16")
    names = cell["reference"].field_names(cell["par"])
    return harness.readings(cell, dict(zip(names, ctrl)), ctrl_steps,
                            ref[0], ref[1])


def calibrate(cell: dict, seeds, control_seeds, log=None):
    import harness
    import run

    run.devices_for(cell["chips"])
    solver = harness.setup(cell, seeds[0])
    out = {"program": {}, "control": {}}
    for seed in seeds:
        t0 = time.perf_counter()
        harness.place_seed(solver, cell, seed)
        harness.reset(solver)
        n = harness.replay(solver)
        prog = solver.global_fields()
        ref = harness.reference_fields(cell, seed, "float32")
        rd = harness.readings(cell, prog, n, ref[0], ref[1])
        out["program"][str(seed)] = rd
        line = {"seed": seed, "program": rd, "steps": n,
                "s": time.perf_counter() - t0}
        if seed in control_seeds:
            line["control"] = out["control"][str(seed)] = \
                control_readings(cell, seed, ref)
        if log is not None:
            print(json.dumps(line), file=log, flush=True)
    keys = sorted(next(iter(out["program"].values())))
    out["lower"] = {k: max(r[k] for r in out["program"].values())
                    for k in keys}
    if out["control"]:
        out["upper"] = {k: min(r[k] for r in out["control"].values())
                        for k in keys}
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--control-seeds", default="")
    ap.add_argument("--out", default="")
    args = ap.parse_args(argv)
    import run

    run.environment()
    import spec

    cell = spec.cell(args.workload)
    seeds = [int(s) for s in args.seeds.split(",") if s]
    control = [int(s) for s in args.control_seeds.split(",") if s]
    res = calibrate(cell, seeds, set(control), log=sys.stderr)
    res["workload"] = args.workload
    if args.out:
        os.makedirs(os.path.dirname(os.path.abspath(args.out)), exist_ok=True)
        with open(args.out, "w") as fh:
            json.dump(res, fh, indent=1)
    print(json.dumps({"lower": res["lower"], "upper": res.get("upper")}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
