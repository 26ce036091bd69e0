#!/usr/bin/env python3
"""Chip smoke: drive pampi_tpu's main path on a TPU, through the CLI.

  python chip_smoke.py             one chip, three phases in one process
  python chip_smoke.py --chips 4   four chips: only the 2x2 distributed run
                                   and the one-chip run it is compared with

One chip (cheapest phase first, so a fault ends the run early):
  parity  configs/dcavity256.par, te cut to 0.1 (about 53 steps in chunks
          of 16, every solve converged to eps): the chip path against the
          dispatch-forced jnp path (fused phases off, jnp solve)
  ns3d    configs/dcavity3d.par: NS-3D cavity, 128³ f32, te cut to 0.1
          (about 26 steps in chunks of 8; the 3-D kernels are separate
          code)
  main    configs/dcavity4096.par: NS-2D lid-driven cavity, 4096² f32,
          Re=1000, itermax-capped solves, 96 steps in three chunks; then
          a second compile of its chunk, from the persistent cache
Four chips:
  mesh    configs/dcavity4096.par with tpu_mesh 2x2 (models/ns2d_dist.py)
          against the same config with tpu_mesh 1 on devices[0]

Every run goes through pampi_tpu.cli.run, the body of
`python -m pampi_tpu <file.par>`. The script fails (exit 1, no JSON line)
when the platform is not tpu, when a check fails, when a chip phase's
dispatch snapshot holds a `jnp (...)` choice, or when the flight record
(PAMPI_TELEMETRY, turned on here) holds a `retry` record. Compile seconds
and ms/step are smoke readings from one run, not a benchmark.

The native layer is rebuilt from native/src with `make -B` before the
package is imported, and loaded from that build only. No child process
touches JAX: the chip belongs to this process.
"""

from __future__ import annotations

import argparse
import json
import os
import subprocess
import sys
import time

REPO = os.path.dirname(os.path.abspath(__file__))
OUT = os.path.join(REPO, "chiprun_out", "chip_smoke")

# Stated bounds. The projection leaves div(u) = dt * r, where r is the
# pressure solve's residual, so the normalised divergence rms(div)·h/U_lid
# is held to twice what the solve's reported residual allows plus an f32
# rounding floor (u differences over h at 24-bit precision).
DIV_RES_FACTOR = 2.0
DIV_F32_FLOOR = 1e-6
# parity: chip vs jnp oracle at 256², both converged to eps=1e-3 each step;
# the paths differ by up to n_inner-1 extra SOR iterations per solve, so
# the fields agree to the solve tolerance, not to the ulp
PARITY_UV_TOL = 2e-3   # max |du|, |dv| over U_lid
PARITY_P_TOL = 2e-3    # max |dp| (mean removed) over max |p|
# mesh: 2x2 vs one chip, same kernels and iteration counts per solve
MESH_TOL = 1e-4        # max |df| over max |f| for u, v, p


class SmokeFailure(Exception):
    pass


def check(cond: bool, what: str) -> None:
    if not cond:
        raise SmokeFailure(what)


def say(msg: str) -> None:
    print(f"chip_smoke: {msg}", flush=True)


class Monitor:
    """Backend compile seconds and persistent-cache hits/misses, from
    JAX's own monitoring events."""

    def __init__(self):
        import jax

        self.compile_s = 0.0
        self.hits = 0
        self.misses = 0

        def on_duration(event, duration, **_kw):
            if event == "/jax/core/compile/backend_compile_duration":
                self.compile_s += duration

        def on_event(event, **_kw):
            if event == "/jax/compilation_cache/cache_hits":
                self.hits += 1
            elif event == "/jax/compilation_cache/cache_misses":
                self.misses += 1

        jax.monitoring.register_event_duration_secs_listener(on_duration)
        jax.monitoring.register_event_listener(on_event)

    def mark(self) -> tuple:
        return self.compile_s, self.hits, self.misses


class Flight:
    """The PAMPI_TELEMETRY JSONL this run writes, read phase by phase."""

    def __init__(self, path: str):
        self.path = path
        self.seen = 0

    def new(self) -> list:
        if not os.path.exists(self.path):
            return []
        with open(self.path) as fh:
            lines = fh.readlines()
        recs = [json.loads(ln) for ln in lines[self.seen:] if ln.strip()]
        self.seen = len(lines)
        return recs


def read_par(name: str, **overrides):
    from pampi_tpu.utils.params import Parameter, read_parameter

    param = read_parameter(os.path.join(REPO, "configs", name), Parameter())
    return param.replace(**overrides)


def run_cli(label, param, mon, flight, write=False):
    """One CLI run of a chip phase; returns (solver, readings). The phase
    must show no `jnp (...)` dispatch choice and no retry record."""
    from pampi_tpu import cli
    from pampi_tpu.utils import dispatch

    dispatch.reset()
    c0, _, _ = mon.mark()
    t0 = time.perf_counter()
    rc, solver = cli.run(param, config=label, write=write)
    wall = time.perf_counter() - t0
    check(rc == 0 and solver is not None, f"{label}: the CLI run exited {rc}")
    recs = flight.new()
    retries = [r for r in recs if r["kind"] == "retry"]
    check(not retries, f"{label}: telemetry retry records {retries}")
    snap = dispatch.snapshot()
    say(f"{label}: dispatch {json.dumps(snap, sort_keys=True)}")
    bad = {k: v for k, v in snap.items() if v.startswith("jnp")}
    check(not bad, f"{label}: jnp dispatch on the chip path: {bad}")
    chunks = [r for r in recs if r["kind"] == "chunk"]
    steady = [r["ms_per_step"] for r in chunks
              if not r["includes_compile"] and r["steps"]]
    info = {
        "wall_s": wall,
        "compile_s": mon.mark()[0] - c0,
        "steps": solver.nt,
        "steady_ms_per_step": (sorted(steady)[len(steady) // 2]
                               if steady else None),
        "last_res": chunks[-1]["res"] if chunks else None,
        "last_dt": chunks[-1]["dt"] if chunks else None,
        "dispatch": snap,
    }
    say(f"{label}: {info['steps']} steps in {wall:.3f} s, backend compile "
        f"{info['compile_s']:.3f} s, steady {info['steady_ms_per_step']} "
        "ms/step (smoke reading, not a benchmark)")
    return solver, info


def divergence(fields: dict, h: tuple):
    """Discrete divergence of the staggered velocity over interior cells
    (fields in the reference [k,] j, i layout with the ghost ring)."""
    import numpy as np

    names = ("u", "v", "w")[:len(h)]
    inner = (slice(1, -1),) * len(h)
    div = 0.0
    for ax, (f, hx) in enumerate(zip(names, reversed(h))):
        a = np.asarray(fields[f], np.float64)
        d = len(h) - 1 - ax  # u varies along the last axis
        lo = list(inner)
        lo[d] = slice(0, -2)
        div = div + (a[inner] - a[tuple(lo)]) / hx
    return div


def check_fields(label, solver, info, h, shape):
    """Finite fields of the full shape, and the projection's divergence
    bound (see DIV_RES_FACTOR)."""
    import numpy as np

    fields = solver.global_fields()
    for name, arr in fields.items():
        check(arr.shape == shape, f"{label}: {name} shape {arr.shape} != "
                                  f"{shape}")
        check(bool(np.isfinite(arr).all()), f"{label}: {name} not finite")
    div = divergence(fields, h)
    hmin = min(h)
    rms = float(np.sqrt(np.mean(div * div))) * hmin
    bound = (DIV_RES_FACTOR * info["last_dt"] * float(np.sqrt(info["last_res"]))
             * hmin + DIV_F32_FLOOR)
    say(f"{label}: fields finite at {shape}; rms(div)·h/U = {rms:.3e} "
        f"(bound {bound:.3e} = {DIV_RES_FACTOR}·dt·sqrt(res)·h + "
        f"{DIV_F32_FLOOR})")
    check(rms <= bound, f"{label}: divergence {rms:.3e} over bound "
                        f"{bound:.3e}")
    return fields


def max_rel_diff(a: dict, b: dict, names, scale=None) -> dict:
    import numpy as np

    out = {}
    for f in names:
        x = np.asarray(a[f], np.float64)
        y = np.asarray(b[f], np.float64)
        if f == "p":  # the Neumann problem's pressure is up to a constant
            x, y = x - x.mean(), y - y.mean()
        s = scale if scale is not None and f != "p" else \
            max(float(np.abs(y).max()), 1e-30)
        out[f] = float(np.abs(x - y).max()) / s
    return out


def peak_bytes() -> int | None:
    import jax

    stats = jax.devices()[0].memory_stats() or {}
    return stats.get("peak_bytes_in_use")


def one_chip(mon, flight) -> None:
    import jax
    import jax.numpy as jnp

    from pampi_tpu.models.ns2d import NS2DSolver

    # parity: chip path vs the dispatch-forced jnp path, converged solves
    param = read_par("dcavity256.par", te=0.1, tpu_chunk=16)
    s, info = run_cli("dcavity256", param, mon, flight, write=True)
    n = param.imax
    chip = check_fields("dcavity256", s, info, (1.0 / n, 1.0 / n),
                        (n + 2, n + 2))
    check(info["dispatch"].get("sor2d", "").startswith("pallas"),
          "dcavity256: the SOR solve must be Pallas")
    from pampi_tpu.utils import dispatch

    dispatch.reset()
    oracle = NS2DSolver(param.replace(tpu_fuse_phases="off"),
                        dtype=jnp.float32)
    oracle._backend = "jnp"
    oracle._chunk_fn = jax.jit(oracle._build_chunk(backend="jnp"))
    oracle.run(progress=False)
    flight.new()
    say(f"parity oracle: dispatch {json.dumps(dispatch.snapshot(), sort_keys=True)}")
    check(oracle.nt == s.nt, f"parity: steps {s.nt} vs oracle {oracle.nt}")
    diff = max_rel_diff(chip, oracle.global_fields(), ("u", "v"), scale=1.0)
    diff.update(max_rel_diff(chip, oracle.global_fields(), ("p",)))
    say(f"parity 256² chip vs jnp after {s.nt} steps: max|du|,|dv| = "
        f"{diff['u']:.3e}, {diff['v']:.3e} (tol {PARITY_UV_TOL}); max|dp| "
        f"rel = {diff['p']:.3e} (tol {PARITY_P_TOL})")
    check(max(diff["u"], diff["v"]) <= PARITY_UV_TOL
          and diff["p"] <= PARITY_P_TOL, f"parity: {diff}")

    # ns3d: the 3-D kernels
    param = read_par("dcavity3d.par", te=0.1, tpu_chunk=8)
    s, info = run_cli("dcavity3d", param, mon, flight)
    check(info["dispatch"].get("sor3d", "").startswith("pallas")
          and info["dispatch"].get("ns3d_phases", "").startswith("pallas"),
          "dcavity3d: the SOR solve and fused phases must be Pallas")
    n = (param.kmax, param.jmax, param.imax)
    check_fields("dcavity3d", s, info,
                 (param.zlength / n[0], param.ylength / n[1],
                  param.xlength / n[2]),
                 tuple(x + 2 for x in n))
    del s

    # main: the north-star config at full width
    param = read_par("dcavity4096.par")
    s, info = run_cli("dcavity4096", param, mon, flight)
    check(info["dispatch"].get("sor2d", "").startswith("pallas")
          and info["dispatch"].get("ns2d_phases", "").startswith("pallas"),
          "dcavity4096: the SOR solve and fused phases must be Pallas")
    n = param.imax
    check_fields("dcavity4096", s, info, (1.0 / n, 1.0 / n), (n + 2, n + 2))
    say(f"peak device bytes (process, all phases so far) {peak_bytes()}")
    # second compile of the same shape: from the persistent cache
    _, h0, m0 = mon.mark()
    jax.clear_caches()
    t0 = time.perf_counter()
    s._chunk_fn.lower(*s.initial_state()).compile()
    dt = time.perf_counter() - t0
    _, h1, m1 = mon.mark()
    say(f"compile cache: second compile of the dcavity4096 chunk took "
        f"{dt:.3f} s, cache hits {h1 - h0}, misses {m1 - m0} "
        f"({'hit' if h1 > h0 else 'not hit'}; dir "
        f"{jax.config.jax_compilation_cache_dir})")
    del s


def four_chips(mon, flight) -> None:
    import jax

    check(len(jax.devices()) >= 4, f"--chips 4 needs 4 devices, JAX has "
                                   f"{len(jax.devices())}")
    param = read_par("dcavity4096.par")
    n = param.imax
    single, info1 = run_cli("dcavity4096 1 chip", param, mon, flight)
    check(single.u.devices() == {jax.devices()[0]},
          f"the 1-chip run sits on {single.u.devices()}")
    one = check_fields("dcavity4096 1 chip", single, info1,
                       (1.0 / n, 1.0 / n), (n + 2, n + 2))
    del single
    dist, info4 = run_cli("dcavity4096 2x2", param.replace(tpu_mesh="2x2"),
                          mon, flight)
    check(info4["dispatch"].get("ns2d_dist", "").startswith("pallas")
          and info4["dispatch"].get("ns2d_dist_phases", "")
          .startswith("pallas"),
          "dcavity4096 2x2: the per-shard solve and phases must be Pallas")
    holders = {sh.device for sh in dist.u.addressable_shards}
    say(f"dcavity4096 2x2: u shards on {sorted(d.id for d in holders)}")
    check(holders == set(jax.devices()[:4]),
          f"2x2: shards on {holders}, not on all four devices")
    four = check_fields("dcavity4096 2x2", dist, info4, (1.0 / n, 1.0 / n),
                        (n + 2, n + 2))
    check(dist.nt == info1["steps"], f"mesh: steps {dist.nt} vs "
                                     f"{info1['steps']}")
    diff = max_rel_diff(four, one, ("u", "v", "p"))
    say(f"mesh 2x2 vs 1 chip after {dist.nt} steps: max rel diff {diff} "
        f"(tol {MESH_TOL})")
    check(max(diff.values()) <= MESH_TOL, f"mesh: {diff}")


def build_native() -> None:
    """Rebuild the native layer from native/src (never a build/ that came
    with the copy) and pin the loader to it."""
    proc = subprocess.run(["make", "-B", "-C", REPO, "TAG=JAX"],
                          capture_output=True, text=True)
    check(proc.returncode == 0, f"native build failed:\n{proc.stderr}")
    os.environ["PAMPI_NATIVE_LIB"] = os.path.join(
        REPO, "build", "JAX", "libpampi_native.so")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1)
    args = ap.parse_args(argv)
    try:
        for need in ("pampi_tpu", "configs", os.path.join("native", "src")):
            check(os.path.exists(os.path.join(REPO, need)),
                  f"{need} not found next to chip_smoke.py: run it from a "
                  "checkout of the repo")
        import jax

        dev = jax.devices()[0]
        check(dev.platform == "tpu", f"platform is {dev.platform!r}, not "
                                     "'tpu': this smoke runs on the chip only")
        build_native()
        os.makedirs(OUT, exist_ok=True)
        os.chdir(OUT)  # the CLI writes its output files to the cwd
        tel = os.path.join(OUT, "telemetry.jsonl")
        if os.path.exists(tel):
            os.remove(tel)
        os.environ["PAMPI_TELEMETRY"] = tel
        sys.path.insert(0, REPO)
        from pampi_tpu.utils import xlacache

        say(f"device {dev.device_kind} x{len(jax.devices())}; compile cache "
            f"{xlacache.enable()}")
        mon = Monitor()
        flight = Flight(tel)
        (four_chips if args.chips == 4 else one_chip)(mon, flight)
    except SmokeFailure as exc:
        print(f"chip_smoke: FAIL: {exc}", file=sys.stderr, flush=True)
        return 1
    devices = jax.devices()
    print(json.dumps({"ok": True, "device": {
        "platform": devices[0].platform, "kind": devices[0].device_kind,
        "count": len(devices)}}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
