"""CLI driver: `python -m pampi_tpu <configFile.par>`.

Parity with the reference's L6 driver convention (`./exe-<TAG> <file.par>`,
assignment-6/src/main.c:21-110): parse argv -> read .par -> echo config ->
run solver -> write outputs -> print walltime. Dispatch on the `name` key:
  poisson           -> 2-D Poisson red-black SOR      (assignment-4)
  dcavity / canal   -> NS-2D time-stepper             (assignment-5)
  canal_obstacle    -> NS-2D canal + flag-masked obstacles (ops/obstacle.py)
  dcavity3d/canal3d -> NS-3D time-stepper             (assignment-6)
"""

from __future__ import annotations

import os
import sys


def main(argv=None) -> int:
    argv = sys.argv if argv is None else argv
    if len(argv) < 2:
        print(f"Usage: {argv[0]} <configFile>  |  {argv[0]} <N> <iter>")
        return 0
    if argv[1] == "--halo-test":
        # halo-exchange debug dump (≙ assignment-6 test.c rank-id checker)
        from .parallel.halo_debug import main as halo_main

        return halo_main(argv)
    if argv[1].isdigit():
        # DMVM mode (≙ assignment-3a/3b CLI: ./exe <N> <iter>); under a
        # PAMPI_COORDINATOR launch the ring spans every process's devices
        from .models.dmvm import main as dmvm_main
        from .parallel import multihost

        with multihost.session():
            return dmvm_main(argv)
    return _run(argv)


def mesh_is_single(param) -> bool:
    """Whether the tpu_mesh key resolves to the single-device path — the
    ONE statement of that policy, shared by `_make_comm` (which builds
    the CartComm otherwise) and the fleet scheduler's per-bucket mode
    decision (`fleet/scheduler._is_dist` must never diverge from the
    comm the template build actually constructs)."""
    import jax

    if len(jax.devices()) == 1:
        return True
    if param.tpu_mesh == "auto":
        return False
    return all(int(t) == 1 for t in param.tpu_mesh.split("x"))


def _make_comm(param, ndims: int):
    """Resolve the tpu_mesh key to a CartComm, or None for single-device
    (the ≙ of ENABLE_MPI=false: same solver API, one process, comm.c:470-488)."""
    import jax

    dims = (
        None
        if param.tpu_mesh == "auto"
        else tuple(int(t) for t in param.tpu_mesh.split("x"))
    )
    if mesh_is_single(param):
        if jax.process_count() > 1:
            # every rank would run the full serial solver and race on the
            # output files; a 1-cell mesh makes no sense distributed
            raise ValueError(
                "tpu_mesh 1 under a multi-process launch: drop the "
                "PAMPI_COORDINATOR env (run single-process) or widen tpu_mesh"
            )
        return None
    from .parallel.comm import CartComm

    # grid extents in mesh-axis order make `auto` prefer factorizations the
    # grid actually divides (e.g. canal.par 200x50 on 8 devices -> (2,4))
    extents = (
        (param.jmax, param.imax) if ndims == 2
        else (param.kmax, param.jmax, param.imax)
    )
    comm = CartComm(ndims=ndims, dims=dims, extents=extents,
                    tiers=param.tpu_mesh_tiers)
    comm.print_config()
    return comm


def _try_build(build):
    """Config errors (bad mesh shape, indivisible grid) get a clean one-line
    report; solver-internal errors keep their traceback."""
    try:
        return build()
    except ValueError as exc:
        print(f"Error: {exc}", file=sys.stderr)
        return None


def _run(argv) -> int:
    from .utils.params import Parameter, read_parameter

    return run(read_parameter(argv[1], Parameter()), config=argv[1])[0]


def run(param, config: str = "", write: bool = True):
    """One whole CLI run of an already-read .par: commInit, compile cache,
    config echo, solve, outputs (`write=False` skips the output files),
    walltime. Returns (exit code, solver); solver is None when the run
    stopped before one was built. chip_smoke.py drives the CLI through
    here so it can check the fields the run ends with."""
    from .utils.params import print_parameter

    # commInit before anything touches devices: under a PAMPI_COORDINATOR
    # launch this joins the process group and makes jax.devices() global;
    # single-process runs no-op (≙ the ENABLE_MPI=false build)
    from .parallel import multihost

    # the whole body runs inside the commInit/commFinalize bracket so a
    # failure anywhere (cache setup, config echo, solver) still shuts the
    # process group down instead of leaving peer ranks blocked
    with multihost.session():
        from .utils import xlacache

        xlacache.enable()  # recompiles of unchanged programs become disk loads

        if param.tpu_dtype == "float64":
            import jax

            jax.config.update("jax_enable_x64", True)
        from .utils import flags as _flags

        _flags.set_default("PAMPI_DTYPE", param.tpu_dtype)

        from .utils import profiling as prof
        from .utils import telemetry

        print_parameter(param)
        prof.init()
        telemetry.start_run(
            tool="cli", config=config, problem=param.name,
            grid=[param.kmax, param.jmax, param.imax],
            solver=param.tpu_solver, dtype=param.tpu_dtype,
        )
        try:
            return _dispatch(param, prof, write)
        finally:
            # always stop an open XProf trace and print the region table, even
            # when the solver or a writer raises — that's the run worth
            # profiling. telemetry.finalize after prof.finalize: the region
            # table is still populated (only reset() clears it) and lands in
            # the JSONL finalize record; both are idempotent vs their atexit
            # hooks
            prof.finalize()
            telemetry.finalize()


def _resume_after_death(param, exc, is3d: bool):
    """The driver's dead-rank policy (`tpu_dead_resume`): on a
    RankDeadError, restore the newest agreed elastic generation onto
    whatever capacity THIS process still owns and finish the run
    degraded (fleet/scheduler.shrink_resume). Returns the completed
    survivor solver, or None when resume is off / not armed / this
    process cannot stand alone — then the structured error plus the
    operator walkthrough is the output, and the caller exits 3.

    Under a real multi-process launch every surviving process lands
    here; an in-place process-group shrink would need a re-elected
    coordinator and dense re-ranking, so the cross-process story is the
    printed relaunch (survivor count + tpu_restart) — the single-process
    shape (one host owning local devices, and the lockstep proof path)
    resumes in-process."""
    import jax

    print(f"Error: {exc}", file=sys.stderr)
    armed = (param.tpu_dead_resume and param.tpu_checkpoint
             and param.tpu_ckpt_elastic
             and os.path.exists(param.tpu_checkpoint))
    if not armed:
        print(
            "dead-rank resume not armed (needs tpu_dead_resume 1 + "
            "tpu_ckpt_elastic 1 + an existing tpu_checkpoint manifest); "
            "resume manually via tpu_restart on the survivor set",
            file=sys.stderr,
        )
        return None
    if jax.process_count() > 1:
        n_alive = (len(exc.survivors) if exc.survivors is not None
                   else jax.process_count() - max(1, len(exc.ranks)))
        print(
            "dead-rank resume across processes is operator-driven: "
            f"relaunch with {n_alive} process(es) on the surviving "
            f"hosts, adding `tpu_restart {param.tpu_checkpoint}` — the "
            "elastic manifest reshards onto the shrunk mesh and the "
            "fault ledger restores the fleet's protocol state",
            file=sys.stderr,
        )
        return None
    from .fleet.scheduler import shrink_resume

    family = "ns3d" if is3d else "ns2d"
    try:
        solver = shrink_resume(param.tpu_checkpoint, param,
                               family=family, dead=exc.ranks,
                               epoch=exc.epoch)
    except (OSError, ValueError, KeyError) as err:
        print(f"Error: dead-rank resume from {param.tpu_checkpoint} "
              f"failed: {err}", file=sys.stderr)
        return None
    print(f"Resumed on the survivor set from {param.tpu_checkpoint} "
          f"(generation {getattr(solver, '_elastic_generation', '?')}) "
          f"at t={solver.t:.4f}; finishing at degraded capacity")
    solver.run()
    return solver


def _dispatch(param, prof, write: bool = True):
    from .utils.timing import get_timestamp

    if param.tpu_solver not in ("sor", "mg", "fft", "sor_lex", "sor_rba",
                                "auto"):
        print(
            "Error: tpu_solver must be auto|sor|mg|fft|sor_lex|sor_rba, "
            f"got {param.tpu_solver!r}",
            file=sys.stderr,
        )
        return 1, None

    from .utils.params import is_3d_config

    ns3d = is_3d_config(param)
    if param.tpu_solver == "sor_rba" and not param.name.startswith("poisson"):
        # the assignment-4 separable-ω oracle; NS pressure solves don't
        # have it (sor_lex IS available on NS-2D — the capped-trajectory
        # ordering oracle, tools/northstar.py match4096)
        print(
            "Error: tpu_solver sor_rba is a Poisson-only oracle mode; "
            "NS problems take sor|sor_lex|mg|fft",
            file=sys.stderr,
        )
        return 1, None
    if param.tpu_solver == "sor_lex" and ns3d:
        print(
            "Error: tpu_solver sor_lex is 2-D only (Poisson and NS-2D); "
            "NS-3D takes sor|mg|fft",
            file=sys.stderr,
        )
        return 1, None

    if param.tpu_chunk < 0 or param.tpu_lookahead < 0:
        print(
            "Error: tpu_chunk and tpu_lookahead must be >= 0 "
            f"(got {param.tpu_chunk}, {param.tpu_lookahead})",
            file=sys.stderr,
        )
        return 1, None

    if (param.tpu_recover_ring < 0 or param.tpu_recover_max < 1
            or not 0.0 < param.tpu_recover_dt_scale <= 1.0
            or param.tpu_retry_replenish < 0):
        print(
            "Error: recovery knobs out of range — need tpu_recover_ring "
            ">= 0, tpu_recover_max >= 1, 0 < tpu_recover_dt_scale <= 1, "
            "tpu_retry_replenish >= 0 (got "
            f"{param.tpu_recover_ring}, {param.tpu_recover_max}, "
            f"{param.tpu_recover_dt_scale}, {param.tpu_retry_replenish})",
            file=sys.stderr,
        )
        return 1, None

    if param.tpu_coord not in ("auto", "on", "off") \
            or param.tpu_ckpt_elastic not in (0, 1):
        print(
            "Error: tpu_coord must be auto|on|off and tpu_ckpt_elastic "
            f"0|1 (got {param.tpu_coord!r}, {param.tpu_ckpt_elastic})",
            file=sys.stderr,
        )
        return 1, None

    if param.tpu_coord_timeout < 0 or param.tpu_dead_resume not in (0, 1):
        print(
            "Error: tpu_coord_timeout must be >= 0 (seconds; 0 disables "
            "the boundary watchdog) and tpu_dead_resume 0|1 (got "
            f"{param.tpu_coord_timeout}, {param.tpu_dead_resume})",
            file=sys.stderr,
        )
        return 1, None

    from .utils import faultinject as _fi

    if _fi.enabled():
        # fault injection is the recovery layer's TEST plane — loud when it
        # leaks into a real run (utils/faultinject.py)
        print(
            "WARNING: PAMPI_FAULTS is set — deterministic fault injection "
            "armed (test-only; unset it for production runs)",
            file=sys.stderr,
        )

    if param.tpu_sor_layout not in ("auto", "checkerboard", "quarters",
                                    "octants"):
        print(
            "Error: tpu_sor_layout must be auto|checkerboard|quarters"
            f"|octants, got {param.tpu_sor_layout!r}",
            file=sys.stderr,
        )
        return 1, None

    if param.obstacles.strip() and param.name.startswith("poisson"):
        # refuse rather than silently simulate an empty box
        print(
            "Error: the obstacles key is supported for NS problems only",
            file=sys.stderr,
        )
        return 1, None

    if param.name.startswith("poisson"):
        from .models.poisson import PoissonSolver

        def build():
            comm = _make_comm(param, ndims=2)
            if comm is None:
                return PoissonSolver(param, problem=2)
            from .models.poisson_dist import DistPoissonSolver

            return DistPoissonSolver(param, comm, problem=2)

        solver = _try_build(build)
        if solver is None:
            return 1, None
        start = get_timestamp()
        with prof.region("solve"):
            it, res = solver.solve()
        end = get_timestamp()
        # parity: solver prints "%d " (no newline), main appends Walltime
        print(f"{it} ", end="")
        if write:
            with prof.region("writeResult"):
                solver.write_result("p.dat")
        print("Walltime %.2fs" % (end - start))
    elif param.name in ("dcavity", "canal", "canal_obstacle", "dcavity3d",
                        "canal3d"):
        from .utils.params import is_3d_config

        is3d = is_3d_config(param)
        if is3d and param.tpu_vtk not in ("ascii", "binary", "sharded"):
            # validate before the run, not in the writer after hours of solve
            print(
                f"Error: tpu_vtk must be ascii|binary|sharded, "
                f"got {param.tpu_vtk!r}",
                file=sys.stderr,
            )
            return 1, None

        def build():
            if is3d:
                comm = _make_comm(param, ndims=3)
                if comm is None:
                    from .models.ns3d import NS3DSolver

                    return NS3DSolver(param)
                from .models.ns3d_dist import NS3DDistSolver

                return NS3DDistSolver(param, comm)
            comm = _make_comm(param, ndims=2)
            if comm is None:
                from .models.ns2d import NS2DSolver

                return NS2DSolver(param)
            from .models.ns2d_dist import NS2DDistSolver

            return NS2DDistSolver(param, comm)

        solver = _try_build(build)
        if solver is None:
            return 1, None
        if is3d:
            from .utils import flags as _flags

            if _flags.verbose():
                # ≙ A6 main.c's VERBOSE-gated printConfig(solver)
                from .utils.params import print_solver_config

                print_solver_config(param, solver.grid, solver.dt_bound)
        from .utils import checkpoint as ckpt

        on_sync = None
        if param.tpu_restart:
            try:
                # either format: legacy .npz or elastic manifest (sniffed)
                ckpt.load_any(param.tpu_restart, solver)
            except (OSError, ValueError, KeyError) as exc:
                # config-class error: same one-line convention as _try_build
                print(f"Error: cannot restart from {param.tpu_restart}: {exc}",
                      file=sys.stderr)
                return 1, None
            print(f"Restarted from {param.tpu_restart} at t={solver.t:.4f}")
        if param.tpu_checkpoint:
            from .parallel.coordinator import coord_armed

            # an armed coordinator owns the checkpoint cadence itself
            # (the agreed ckpt vote at chunk boundaries — models/_driver.
            # coord_ckpt_cadence); wiring the counter-based writer too
            # would double-write every cadence point
            if not coord_armed(param):
                on_sync = ckpt.periodic_writer(
                    param.tpu_checkpoint, param.tpu_ckpt_every,
                    save=ckpt.writer_for(param),
                )
        start = get_timestamp()
        from .parallel.coordinator import RankDeadError

        try:
            with prof.region("timeloop"):
                solver.run(on_sync=on_sync)
        except RankDeadError as exc:
            # a peer stopped answering the boundary allgather: the
            # watchdog + membership round turned the wedge into this
            # structured, fleet-symmetric verdict. Shrink to the
            # survivors when the run armed the elastic resume path.
            solver = _resume_after_death(param, exc, is3d)
            if solver is None:
                return 3, None
        end = get_timestamp()
        print("Solution took %.2fs" % (end - start))
        if param.tpu_checkpoint:
            ckpt.writer_for(param)(param.tpu_checkpoint, solver)
        if write:
            with prof.region("writeResult"):
                if not is3d:
                    solver.write_result("pressure.dat", "velocity.dat")
                elif param.tpu_vtk == "sharded":
                    if hasattr(solver, "write_result_sharded"):
                        solver.write_result_sharded()
                    else:  # single device: binary writer = same bytes
                        solver.write_result(fmt="binary")
                else:
                    solver.write_result(fmt=param.tpu_vtk)
    else:
        print(f"Unknown problem name: {param.name}", file=sys.stderr)
        return 1, None
    return 0, solver


if __name__ == "__main__":
    raise SystemExit(main())
