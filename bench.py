"""Headline benchmark: lattice-site updates/sec/chip, Poisson 4096² red-black
SOR (the BASELINE.json metric).

Prints FOUR JSON lines:
  {"metric": "lattice_site_updates_per_sec_per_chip_poisson4096_rbsor", ...}
  {"metric": "ns2d_dcavity4096_ms_per_step", "value": ms, "solve_ms": ...,
   "nonsolve_ms": ..., "phases": <dispatch>, ...}
  {"metric": "ns2d_obstacle2048x512_ms_per_step", ...}  (PR 2: the fused
   obstacle variant's decomposition; ragged/dist twins live in
   tools/perf_ragged.py and tools/perf_obsdist.py)
  {"metric": "mg_launches_per_cycle", "value": N, "mg_dispatch": ...,
   "ladder_launches": ...}  (ISSUE 16: the fused V-cycle's static launch
   census — 2 with the DOWN/UP cycle kernels dispatched)
plus the ISSUE 17 serving/fusion lines: TWO "ns2d_small_ms_per_step"
lines (64² and 256² serving-regime dcavity, K=4 fused chunk, with the
historical one-step chunk's ms/step on the same line for the measured
win) and one "launches_per_step" line (static Pallas census of a traced
K=4 chunk divided by K — the < 3/step fusion-contract number).

The second line is the metric the fused step-phase kernels move (round 6):
the NS-2D north-star step time WITH its solve/non-solve decomposition, so
BENCH_*.json tracks the launch-overhead share directly — the round-5
artifact showed the Poisson kernel already at the vector-issue wall while
the non-solve phase chain (6.4 ms/step measured vs ~0.8 ms HBM-bound) was
the swing term the headline number could not see. Off-TPU the NS line runs
a 256² scaled-down twin of the same config (jnp phases, rate ~3 orders
lower — trend data only, like the Poisson line's off-TPU mode).

Method: 4096² grid, float32 (TPU-native), 9600 timed red-black iterations in
ONE dispatch (fixed count via fori_loop — steady-state throughput, no
convergence check), best-of-12 dispatches after one warm-up; one update =
one interior cell relaxed once (red+black covers each cell exactly once per
iteration, matching the reference's per-iteration cell count). The pallas
backend runs the temporal-blocked kernel (N_INNER red-black iterations +
Neumann BCs per HBM sweep, ops/sor_pallas.py `_tblock_kernel`) —
numerically identical to per-iteration stepping (tests/test_sor_pallas.py).
Off-TPU the counts scale down ~50× (CPU throughput is ~3 orders lower);
those lines are CPU trend data, not chip numbers. A phase that fails exits
the run non-zero: there is no fallback to the jnp path.

vs_baseline: the reference publishes no numbers (SURVEY.md §6). Baseline is
the measured throughput of the reference's own assignment-4 C solver
(gcc -O3 -march=native, lexicographic `solve`, 4096², 20 fixed iterations)
on this container's host CPU: 1.65e8 updates/s/core, linearly scaled to the
8-rank MPI baseline BASELINE.json names => 1.32e9 updates/s. Regenerate with
tools/measure_baseline.sh.
"""

import json
import sys
import time

sys.path.insert(0, __file__.rsplit("/", 1)[0])

import jax
import jax.numpy as jnp
from jax import lax

from pampi_tpu.models.poisson import init_fields, make_rb_loop
from pampi_tpu.utils import xlacache
from pampi_tpu.utils.params import Parameter

BASELINE_8RANK_UPDATES_PER_S = 1.32e9  # see module docstring

N = 4096
# ITERS sizes ONE dispatch: 9600 iterations of the quarters kernel carry
# about a second of device work, so per-dispatch overhead stays a small
# share of the timed window.
ITERS = 9600
N_INNER = 16  # temporal-blocking depth. The auto layout dispatches the
# QUARTER-decomposition kernel (ops/sor_quarters.py — all lanes productive,
# uniform shifts); at n_inner=16 the maker's default block height is 128
# quarter-rows (= 256 grid rows). Round-3 depth sweep: n16/brq128 ran
# ~1.6x the n8/brq64 default under identical conditions. The timed loop
# runs (ITERS // eff) * eff iterations and divides by exactly that count


def _timed_run(backend: str):
    on_tpu = jax.default_backend() == "tpu"
    iters = ITERS if on_tpu else 100
    reps = 12 if on_tpu else 3
    param = Parameter(imax=N, jmax=N, tpu_dtype="float32")
    p, rhs = init_fields(param, problem=2, dtype=jnp.float32)
    # prep carries the pallas padded layout through the loop (identity on
    # jnp); eff is the iterations one step call ACTUALLY performs — the jnp
    # path steps singly regardless of N_INNER
    step, prep, _post, eff = make_rb_loop(
        N, N, 1.0 / N, 1.0 / N, 1.9, jnp.float32, backend=backend,
        n_inner=N_INNER,
    )
    p, rhs = prep(p), prep(rhs)
    outer = iters // eff
    iters_done = outer * eff  # the count the rate formula divides by

    @jax.jit
    def run_iters(p, rhs):
        def body(_, carry):
            p, _res = carry
            return step(p, rhs)

        return lax.fori_loop(0, outer, body, (p, jnp.asarray(0.0, jnp.float32)))

    out = run_iters(p, rhs)
    float(out[1])  # warm-up + compile; scalar readback forces completion
    best = float("inf")
    for _ in range(reps):
        t0 = time.perf_counter()
        out = run_iters(p, rhs)
        float(out[1])  # a host readback of the carried residual: the fence
        best = min(best, time.perf_counter() - t0)
    return best, iters_done


def _step_decomposition_line(param, metric, config, steps, reps):
    """Chunk-timed NS-2D ms/step + the TPU-only solve/non-solve split —
    the ONE protocol every bench step line uses (compile + warm with a
    scalar-readback fence, best-of-reps; the solve share via
    NS2DSolver.time_solve_ms, also what tools/northstar.py records —
    no hand-copied phase wiring to silently diverge). `param` must carry
    tpu_flat_solve=1 so every solve runs exactly itermax iterations and
    the step - solve subtraction is well-defined."""
    from pampi_tpu.models.ns2d import NS2DSolver
    from pampi_tpu.utils import dispatch, telemetry, xprof

    assert param.tpu_flat_solve, "decomposition needs the flat solve"
    s = NS2DSolver(param, dtype=jnp.float32)
    state = s.initial_state()
    out = s._chunk_fn(*state)
    float(out[3])  # compile + warm-up; scalar readback is the fence
    best = float("inf")
    # PAMPI_XPROF: device-trace the timed window (no-op when unset) —
    # the per-kernel attribution behind the headline number
    with xprof.capture(metric, steps=steps * reps):
        for _ in range(reps):
            t0 = time.perf_counter()
            out = s._chunk_fn(*state)
            float(out[3])
            best = min(best, time.perf_counter() - t0)
    step_ms = best / steps * 1e3
    line = {
        "metric": metric,
        "value": round(step_ms, 3),
        "unit": "ms/step",
        "phases": dispatch.last("ns2d_phases"),
        "steps_timed": steps,
        "config": config,
    }
    if jax.default_backend() != "tpu":
        # the decomposition is TPU-only: off-TPU the standalone jitted
        # solve compiles SLOWER than the same solve fused into the chunk
        # program (measured 91-120 vs 80 ms/step at 256² — XLA:CPU
        # whole-program optimization), so step - solve would go negative;
        # on TPU both are the same pallas kernel and the subtraction is
        # meaningful
        line = {**line, "solve_ms": None, "nonsolve_ms": None,
                "decomposition_note": "TPU-only (see bench.py)"}
    else:
        solve_ms = s.time_solve_ms(reps=reps)
        line = {**line, "solve_ms": round(solve_ms, 3),
                "nonsolve_ms": round(step_ms - solve_ms, 3)}
    # the decomposition as shared telemetry spans + the headline metric
    # record (no-ops when PAMPI_TELEMETRY is unset)
    telemetry.emit_decomposition(metric, step_ms, line["solve_ms"],
                                 line["nonsolve_ms"],
                                 phases=line["phases"], config=config)
    telemetry.emit("metric", **line)
    return line


def _ns2d_step_line():
    """NS-2D dcavity step time + solve/non-solve decomposition (the
    north-star config at 4096² on TPU, a 256² twin off-TPU)."""
    from pampi_tpu.utils.params import Parameter as _P

    on_tpu = jax.default_backend() == "tpu"
    n = 4096 if on_tpu else 256
    steps = 128 if on_tpu else 8
    param = _P(
        name="dcavity", imax=n, jmax=n, re=1000.0, te=1e9, tau=0.5,
        itermax=100, eps=1e-3, omg=1.7, gamma=0.9, tpu_dtype="float32",
        tpu_sor_inner=16, tpu_flat_solve=1, tpu_chunk=steps,
    )
    return _step_decomposition_line(
        param, f"ns2d_dcavity{n}_ms_per_step",
        f"dcavity {n}^2 f32 Re=1000 itermax=100 n_inner=16 flat",
        steps, 6 if on_tpu else 3,
    )


def _ns2d_obstacle_step_line():
    """The obstacle twin of _ns2d_step_line (PR 2: obstacle flag fields now
    ride the fused phase megakernels everywhere): flag-masked canal at the
    BASELINE obsdist geometry (2048x512 on TPU, a 256x64 twin off-TPU)."""
    from pampi_tpu.utils.params import Parameter as _P

    on_tpu = jax.default_backend() == "tpu"
    ni, nj = (2048, 512) if on_tpu else (256, 64)
    steps = 64 if on_tpu else 8
    param = _P(
        name="canal_obstacle", imax=ni, jmax=nj,
        xlength=16.0, ylength=4.0, re=100.0, te=1e9, tau=0.5,
        itermax=100, eps=1e-3, omg=1.7, gamma=0.9, u_init=1.0,
        bcLeft=3, bcRight=3, bcTop=1, bcBottom=1,
        obstacles="6.0,1.5,10.0,2.5",
        tpu_dtype="float32", tpu_solver="sor", tpu_sor_inner=16,
        tpu_flat_solve=1, tpu_chunk=steps,
    )
    return _step_decomposition_line(
        param, f"ns2d_obstacle{ni}x{nj}_ms_per_step",
        f"canal_obstacle {ni}x{nj} f32 Re=100 itermax=100 n_inner=16 flat",
        steps, 6 if on_tpu else 3,
    )


def _ns2d_small_step_line():
    """Small-grid serving-regime step lines (ISSUE 17): at 64²/256² the
    per-step envelope (loop plumbing, metrics latch, dispatch floor on
    TPU) is a first-order cost the 4096² north-star line cannot see —
    exactly the budget the K-fused chunk amortizes. Runs the SAME
    protocol as the big line (`_step_decomposition_line`) with the
    production K forced on (`tpu_chunk_fuse=4` traces the scan on any
    backend), and attaches the historical one-step-per-body chunk's
    ms/step to the same line so the artifact carries the measured win,
    not just the fused number. One line per grid, one shared metric
    name — the normalized trend series gates on the first (64²) point;
    the 256² twin stays a parsed block keyed by its config string."""
    from pampi_tpu.models.ns2d import NS2DSolver
    from pampi_tpu.utils import dispatch
    from pampi_tpu.utils.params import Parameter as _P

    lines = []
    for n in (64, 256):
        steps = 16

        def small_param(fuse):
            return _P(
                name="dcavity", imax=n, jmax=n, re=100.0, te=1e9,
                tau=0.5, itermax=20, eps=1e-3, omg=1.7, gamma=0.9,
                tpu_dtype="float32", tpu_sor_inner=8, tpu_flat_solve=1,
                tpu_chunk=steps, tpu_chunk_fuse=fuse,
            )

        # one serving-regime chunk is ~10 ms of work — the opposite end
        # of the latency-floor spectrum from the seconds-long headline
        # dispatches, so best-of-MANY cheap reps is what amortizes the
        # scheduler jitter here (the Poisson line's best-of-12 logic)
        reps = 24
        line = _step_decomposition_line(
            small_param("4"), "ns2d_small_ms_per_step",
            f"dcavity {n}^2 f32 serving-regime itermax=20 flat K=4",
            steps, reps,
        )
        line["chunk_fuse"] = dispatch.last("ns2d_chunk_fuse")
        # the A/B the fusion moves: the identical config at the
        # historical chunk (tpu_chunk_fuse=off — bitwise the pre-ISSUE-17
        # trace), timed with the same fence/best-of protocol
        s = NS2DSolver(small_param("off"), dtype=jnp.float32)
        state = s.initial_state()
        out = s._chunk_fn(*state)
        float(out[3])
        best = float("inf")
        for _ in range(reps):
            t0 = time.perf_counter()
            out = s._chunk_fn(*state)
            float(out[3])
            best = min(best, time.perf_counter() - t0)
        line["historical_ms_per_step"] = round(best / steps * 1e3, 3)
        lines.append(line)
    return lines


def _launches_per_step_line():
    """Static launches-per-step census (ISSUE 17): the Pallas launch
    count of ONE traced K-fused chunk divided by K — the scan body
    traces once, so the static count covers K steps and the quotient is
    the per-step launch budget the fusion contract pins (< 3 for K ≥ 2,
    enforced by analysis/jaxprcheck.check_config). Counted from the
    standard-matrix `ns2d_fused_fft_k4` config (forced K=4, so the scan
    traces on any backend) — exact, no timing, same census protocol as
    `_mg_launch_line`."""
    from pampi_tpu.analysis import jaxprcheck as jc
    from pampi_tpu.utils import telemetry

    cfg = next(c for c in jc.standard_configs()
               if c.name == "ns2d_fused_fft_k4")
    tc = jc.trace_config(cfg)
    k = jc.chunk_fuse_k(tc.decisions)
    n_launch = jc.count_prim(tc.jaxpr.jaxpr, "pallas_call")
    line = {
        "metric": "launches_per_step",
        "value": n_launch / k,
        "unit": "launches/step",
        "chunk_fuse_dispatch": tc.decisions.get("ns2d_chunk_fuse"),
        "pallas_calls": n_launch,
        "k": k,
        "config": cfg.name,
    }
    telemetry.emit("metric", **line)
    return line


def _mg_launch_line():
    """The mg launch census (ISSUE 16): how many Pallas launches ONE
    V-cycle costs at the north-star mg geometry, counted STATICALLY from
    the traced cycle program (analysis/jaxprcheck.count_prim) — exact on
    any backend, no timing. The fused cycle pins 2 (DOWN + UP with the
    exact jnp bottom between); `ladder_launches` records the per-level
    ladder's count of the same plan for the amortization ratio (0 off-TPU
    where the ladder's smoothers stay jnp). Rides the same telemetry
    metric protocol as the step lines; the trend gate
    (tools/bench_trend.NAME_DIRECTIONS) holds the count down."""
    from pampi_tpu.analysis.jaxprcheck import count_prim
    from pampi_tpu.ops.multigrid import make_mg_vcycle_2d
    from pampi_tpu.utils import dispatch, telemetry

    on_tpu = jax.default_backend() == "tpu"
    # off-TPU: the smallest plain grid with a multi-level plan at the
    # default DCT-bottom budget (512² -> 256²), so the census is real
    n = 4096 if on_tpu else 512

    def cycle_launches(fused):
        vc = make_mg_vcycle_2d(n, n, 1.0 / n, 1.0 / n, jnp.float32,
                               fused=fused)
        z = jnp.zeros((n + 2, n + 2), jnp.float32)
        return count_prim(jax.make_jaxpr(vc)(z, z).jaxpr, "pallas_call")

    ladder = cycle_launches("off")
    fused = cycle_launches("on")
    line = {
        "metric": "mg_launches_per_cycle",
        "value": fused,
        "unit": "launches/cycle",
        "mg_dispatch": dispatch.last("mg2d_fused"),
        "ladder_launches": ladder,
        "config": f"dcavity {n}^2 f32 mg vcycle",
    }
    telemetry.emit("metric", **line)
    return line


def main() -> int:
    from pampi_tpu.utils import telemetry

    xlacache.enable()
    telemetry.start_run(tool="bench")
    dt, iters = _timed_run("auto")
    ups = N * N * iters / dt
    headline = {
        "metric": "lattice_site_updates_per_sec_per_chip_poisson4096_rbsor",
        "value": ups,
        "unit": "updates/s",
        "vs_baseline": ups / BASELINE_8RANK_UPDATES_PER_S,
        "backend": "pallas" if jax.default_backend() == "tpu" else "jnp",
    }
    telemetry.emit("metric", **headline)
    print(json.dumps(headline), flush=True)
    failed = []
    for name, phase in (("ns2d step", _ns2d_step_line),
                        ("ns2d obstacle step", _ns2d_obstacle_step_line),
                        ("mg launch", _mg_launch_line),
                        ("ns2d small step", _ns2d_small_step_line),
                        ("launches-per-step", _launches_per_step_line)):
        try:
            out = phase()
        except Exception as exc:  # lint: allow(broad-except) — report every failed phase, then exit non-zero
            print(f"{name} line failed ({type(exc).__name__}: {exc})",
                  file=sys.stderr)
            failed.append(name)
            continue
        for line in out if isinstance(out, list) else [out]:
            print(json.dumps(line), flush=True)
    return 1 if failed else 0

if __name__ == "__main__":
    sys.exit(main())
