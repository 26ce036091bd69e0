#!/usr/bin/env python3
"""One run of one benchmark cell on the chips of this machine.

  python3 benchmark/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Set-up (timed as `setup_s`, from the start of this script): imports,
device check, the CLI run that builds the case and compiles or loads its
chunk program (and runs the segment once from the published zero state),
and the placing of the seeded initial fields. The window then replays
the segment, each replay restored to the seeded state first, until
`--seconds` have passed; `ms_per_step` is the sum of the fenced replay
walls over the steps they advanced. With `--trace 1` a profiler trace
covers the first replays of the window and the per-layer metrics are
reduced from it; the end-to-end metrics are then not reported.

After the window the fleet's peak device memory is read, the program's
state is freed, and the configuration's reference
(benchmark/references/<config>.py) runs the same segment from the same
seeded fields; `correct` holds each gap to the cell's limit
(benchmark/limits/<cell>.json). The last stdout line is the
result as JSON; the last stderr lines are the compared numbers with
their limits.

No TPU, or fewer chips than the cell asks for: exit 1, no result.
"""

from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import contextlib  # noqa: E402
import gc  # noqa: E402
import json  # noqa: E402
import math  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
OUT = os.path.join(ROOT, ".bench_out")
TRACE_DIR = os.path.join(OUT, "trace")


class NoDevice(Exception):
    pass


def say(msg: str) -> None:
    print(f"bench: {msg}", file=sys.stderr, flush=True)


def environment() -> None:
    """Program switches off, the compile cache at a fixed path inside the
    checkout, the TPU runtime's logs inside the checkout too."""
    import harness

    for var in harness.PROGRAM_SWITCHES:
        os.environ.pop(var, None)
    os.environ["JAX_COMPILATION_CACHE_DIR"] = os.path.join(ROOT, ".jax_cache")
    os.environ["TPU_LOG_DIR"] = os.path.join(OUT, "tpu_logs")
    os.makedirs(os.environ["TPU_LOG_DIR"], exist_ok=True)
    if ROOT not in sys.path:
        sys.path.insert(0, ROOT)


def devices_for(chips: int):
    import jax

    devs = jax.devices()
    if devs[0].platform != "tpu":
        raise NoDevice(f"platform is {devs[0].platform!r}: this benchmark "
                       "measures a TPU only")
    if len(devs) < chips:
        raise NoDevice(f"the cell asks for {chips} chips, JAX has "
                       f"{len(devs)}")
    return devs


def measure(cell: dict, seed: int, seconds: float, trace: bool) -> dict:
    """The whole run of a loaded cell; returns the result object."""
    import jax

    import harness
    import spec
    import tracered

    devs = devices_for(cell["chips"])
    used = devs[:cell["chips"]]
    mon = harness.Monitor()
    from pampi_tpu.utils import dispatch

    dispatch.reset()
    t_dev = time.perf_counter() - T_START
    solver = harness.setup(cell, seed)
    say(f"dispatch {json.dumps(dispatch.snapshot(), sort_keys=True)}")
    say(f"set-up: imports and device {t_dev:.3f} s, CLI build and warm "
        f"segment and seeded fields {time.perf_counter() - T_START - t_dev:.3f} s")
    compile_setup = mon.compile_s
    compiles_setup = mon.compiles
    traffic = cell["traffic_file"]
    trace_s = float(traffic.get("trace_seconds", 2.0))
    ann = jax.profiler.TraceAnnotation if trace else (
        lambda _name: contextlib.nullcontext())

    setup_s = time.perf_counter() - T_START
    walls, steps = [], []
    traced_steps = 0
    tracing = False
    if trace:
        shutil.rmtree(TRACE_DIR, ignore_errors=True)
        os.makedirs(TRACE_DIR, exist_ok=True)
        jax.profiler.start_trace(TRACE_DIR)
        tracing = True
    w0 = time.perf_counter()
    while True:
        with ann("bench.reset"):
            harness.reset(solver)
        with ann("bench.replay"):
            t0 = time.perf_counter()
            n = harness.replay(solver)
            walls.append(time.perf_counter() - t0)
        steps.append(n)
        if tracing:
            traced_steps += n
            if time.perf_counter() - w0 >= trace_s:
                jax.profiler.stop_trace()
                tracing = False
        if time.perf_counter() - w0 >= seconds:
            break
    if tracing:
        jax.profiler.stop_trace()
    window_compiles = mon.compiles - compiles_setup
    peak = harness.peak_bytes(used)
    prog = solver.global_fields()
    prog_steps = solver.nt
    del solver
    gc.collect()
    jax.clear_caches()

    ref, ref_steps = harness.reference_fields(
        cell, seed, cell["par"].get("tpu_dtype", "float32"))
    values = harness.readings(cell, prog, prog_steps, ref, ref_steps)
    checks = harness.judge(values, cell["limits"]["limits"])
    bad_replays = sum(1 for s in steps if s != ref_steps)
    correct = harness.passed(checks) and bad_replays == 0
    say(f"window: {len(walls)} replays, {sum(steps)} steps in "
        f"{sum(walls):.3f} s of replay wall; backend compiles in the "
        f"window {window_compiles}; set-up compile {compile_setup:.3f} s "
        f"({compiles_setup} programs, cache hits {mon.hits}, misses "
        f"{mon.misses})")

    bench = spec.benchmark()
    dev = used[0]
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(devs), "memory_peak_bytes": peak}
    result = {"correct": bool(correct), "attempted": len(walls),
              "failed": bad_replays + (0 if harness.passed(checks) else 1)}
    if not trace:
        units = {m["name"]: m["unit"]
                 for m in spec.metrics_for(bench, cell["name"], "end_to_end")}
        values_e2e = {
            "setup_s": setup_s,
            "ms_per_step": 1e3 * sum(walls) / max(1, sum(steps)),
            "peak_hbm_gb": peak / 1e9,
        }
        result["metrics"] = {k: {"value": v, "unit": units[k]}
                             for k, v in values_e2e.items() if k in units}
    else:
        xp = tracered.xplane_file(TRACE_DIR)
        if xp is None:
            raise RuntimeError(f"the profiler left no xplane file under "
                               f"{TRACE_DIR}")
        red = tracered.reduce(tracered.load(xp), spec.layer_rules())
        shutil.rmtree(TRACE_DIR, ignore_errors=True)
        ctx = tracered.Context(red, traced_steps, cell["par"],
                               spec.peaks(dev.device_kind), compile_setup,
                               cell["chips"])
        metrics = {}
        for m in spec.metrics_for(bench, cell["name"], "per_layer"):
            val = spec.reader(m["name"])(ctx)
            if val is not None and math.isfinite(val):
                metrics[m["name"]] = {"value": val, "unit": m["unit"]}
        result["metrics"] = metrics
        device["busy_s"] = ctx.busy_s() or 0.0
        device["window_s"] = ctx.window_s() or 0.0
        result["breakdown"] = {"device_ops": red["top_ops"],
                               "idle_gaps": red["idle_gaps"]}
    result["device"] = device
    result["checks"] = checks
    return result


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    if args.seed < 0:
        ap.error("--seed must be a non-negative integer")
    if not os.path.isdir(os.path.join(ROOT, "pampi_tpu")):
        say(f"no pampi_tpu package next to {HERE}: run from a checkout of "
            "the repository")
        return 2
    sys.path.insert(0, HERE)
    environment()
    import spec

    cell = spec.cell(args.workload)
    try:
        result = measure(cell, args.seed, args.seconds, bool(args.trace))
    except NoDevice as exc:
        say(f"FAIL: {exc}")
        return 1
    for name, c in result["checks"].items():
        ok = "ok" if c["value"] <= c["limit"] else "FAIL"
        print(f"check {name} {c['value']!r} limit {c['limit']!r} {ok}",
              file=sys.stderr, flush=True)
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
