"""Set-up, replay and comparison of one cell, shared by run.py (the timed
runs) and calibrate.py (the readings the correctness limits are set from).

The system under test is reached through its CLI entry,
`pampi_tpu.cli.run(param, write=False)`, which builds the solver, compiles
(or loads) its chunk program and runs the segment once from the published
zero initial state. Each replay then restores the seeded initial state
and runs the same segment through the solver's own `run`, the CLI's time
loop (models/_driver.drive_chunks).

Everything that depends on the configuration's physics comes from its
reference module, `cell["reference"]` (benchmark/references/<config>.py):
which .par settings it covers, the field names, the seeded initial
fields, the reference segment, and optionally the cells compared.
"""

from __future__ import annotations

import contextlib
import sys

import numpy as np

# the program's own switches: each would compile a different chunk (or
# arm test-only faults), so every run clears them
PROGRAM_SWITCHES = ("PAMPI_TELEMETRY", "PAMPI_XPROF", "PAMPI_PROFILE",
                    "PAMPI_FAULTS")


class Monitor:
    """Backend compile seconds and persistent-cache hits and misses, from
    JAX's own monitoring events."""

    def __init__(self):
        import jax

        self.compile_s = 0.0
        self.compiles = 0
        self.hits = 0
        self.misses = 0

        def on_duration(event, duration, **_kw):
            if event == "/jax/core/compile/backend_compile_duration":
                self.compile_s += duration
                self.compiles += 1

        def on_event(event, **_kw):
            if event == "/jax/compilation_cache/cache_hits":
                self.hits += 1
            elif event == "/jax/compilation_cache/cache_misses":
                self.misses += 1

        jax.monitoring.register_event_duration_secs_listener(on_duration)
        jax.monitoring.register_event_listener(on_event)


def parameter(par: dict):
    """A pampi_tpu Parameter holding exactly the .par keys of the run."""
    from pampi_tpu.utils.params import Parameter

    param = Parameter()
    for key, val in par.items():
        cur = getattr(param, key)  # an unknown key is an error
        setattr(param, key, type(cur)(val))
    param.seen_keys = tuple(par)
    return param


def setup(cell: dict, seed: int):
    """Build the case through the CLI entry and place the seeded initial
    fields; returns the solver."""
    from pampi_tpu import cli

    par = cell["par"]
    cell["reference"].check_covered(par)
    with contextlib.redirect_stdout(sys.stderr):
        rc, solver = cli.run(parameter(par), config=cell["name"], write=False)
    if rc != 0 or solver is None:
        raise RuntimeError(f"the CLI run of {cell['name']} exited {rc}")
    return place_seed(solver, cell, seed)


def place_seed(solver, cell: dict, seed: int):
    """Give the solver the seeded initial fields, placed as its own
    (possibly sharded) arrays, and remember them for the replays."""
    ref, par = cell["reference"], cell["par"]
    names = ref.field_names(par)
    fields = ref.initial_fields(par, cell["traffic_file"], seed)
    solver.set_global_fields(dict(zip(names, fields)))
    solver._bench_initial = {f: getattr(solver, f) for f in names}
    del fields
    return solver


def reset(solver) -> None:
    """Restore the seeded initial state (harness work, outside the timed
    sum)."""
    for f, arr in solver._bench_initial.items():
        setattr(solver, f, arr)
    solver.t, solver.nt = 0.0, 0


def replay(solver):
    """One timed replay of the segment: the solver's own run, fenced on
    its output fields. Returns the number of steps it advanced."""
    import jax

    solver.run(progress=False)
    jax.block_until_ready([getattr(solver, f) for f in solver._bench_initial])
    return solver.nt


def peak_bytes(devices) -> int:
    """peak_bytes_in_use on the fullest of the devices."""
    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use", 0)
             for d in devices]
    return int(max(peaks))


def gaps(prog: dict, ref: tuple, names, compared=None) -> dict:
    """Per field, the largest gap between the program's and the
    reference's interior values over the reference's largest magnitude
    (pressure with its mean over those cells removed: the Neumann problem
    fixes it only up to a constant). `compared`, a boolean array over the interior cells,
    keeps the cells it marks; without it every interior cell counts."""
    out = {}
    for f, r in zip(names, ref):
        inner = (slice(1, -1),) * r.ndim
        x = np.asarray(prog[f], np.float64)[inner]
        y = np.asarray(r, np.float64)[inner]
        if compared is not None:
            x, y = x[compared], y[compared]
        if f == "p":
            x = x - x.mean()
            y = y - y.mean()
        if not np.isfinite(x).all():
            out[f"{f}_gap"] = float("inf")
            continue
        scale = max(float(np.abs(y).max()), 1e-30)
        out[f"{f}_gap"] = float(np.abs(x - y).max()) / scale
    return out


def reference_fields(cell: dict, seed: int, dtype_name: str):
    """The reference segment from the seed's initial fields, in the
    stated storage type; returns (fields as numpy float32, steps)."""
    import jax.numpy as jnp

    ref, par = cell["reference"], cell["par"]
    fields = ref.initial_fields(par, cell["traffic_file"], seed)
    out, steps = ref.run_segment(par, fields, getattr(jnp, dtype_name))
    return tuple(np.asarray(a) for a in out), steps


def readings(cell: dict, prog: dict, prog_steps: int, ref: tuple,
             ref_steps: int) -> dict:
    """The numbers the limits hold: each field's gap over the cells the
    reference module compares, and the difference in steps."""
    mod, par = cell["reference"], cell["par"]
    compared = getattr(mod, "compared", None)
    if compared is not None:
        compared = np.asarray(compared(par), bool)
    out = gaps(prog, ref, mod.field_names(par), compared)
    out["steps_gap"] = float(abs(prog_steps - ref_steps))
    return out


def judge(values: dict, limits: dict) -> dict:
    """Each reading beside its limit; a reading with no limit is an
    error of the cell's files."""
    checks = {}
    for name, val in values.items():
        if name not in limits:
            raise KeyError(f"no limit for {name!r} in the cell's limits file")
        checks[name] = {"value": val, "limit": limits[name]}
    return checks


def passed(checks: dict) -> bool:
    return all(c["value"] <= c["limit"] for c in checks.values())
