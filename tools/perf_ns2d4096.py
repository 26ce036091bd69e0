"""NS-2D steady-step timing at the north-star grid (4096^2 f32), all three
pressure solvers under ONE protocol, so the BASELINE.md row compares like
with like.

Protocol: dcavity Re=1000, tau=0.5, eps=1e-3, itermax=100, f32. Build the
jitted step, run 5 settle steps (compile + let dt/p leave the cold-start
state), then measure by TWO-POINT differencing of chained-step dispatches:
per-step = (t(k_b) − t(k_a)) / (k_b − k_a), with k_b sized so the dispatch
carries ≥ ~1 s of work: differencing cancels the per-dispatch latency
exactly. Steps chain through the loop carry, so
they serialize naturally. Best-of-REPS on each term suppresses jitter.

Each measured row is also a shared telemetry span record
(utils/telemetry.emit_span; no-op unless PAMPI_TELEMETRY is set), so this
tool's output aggregates through tools/telemetry_report.py like every
other perf tool instead of living only in ad-hoc prints.

Run on the real chip:  python tools/perf_ns2d4096.py [solvers...]
Defaults to: sor fft mg.
"""

import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax
import jax.numpy as jnp

from pampi_tpu.utils.params import Parameter

N = 4096
SETTLE = 5
REPS = 8


def measure(solver: str) -> float:
    from pampi_tpu.models.ns2d import NS2DSolver

    # "sor:quarters" / "sor:checkerboard" pins the SOR layout (default auto)
    layout = "auto"
    if ":" in solver:
        solver, layout = solver.split(":", 1)
    param = Parameter(
        name="dcavity", imax=N, jmax=N, re=1000.0, te=10.0, tau=0.5,
        itermax=100, eps=1e-3, omg=1.7, gamma=0.9, tpu_dtype="float32",
        tpu_solver=solver, tpu_sor_layout=layout,
    )
    s = NS2DSolver(param, dtype=jnp.float32)
    step = s._build_step()

    def k_steps(k):
        @jax.jit
        def run(state):
            return jax.lax.fori_loop(0, k, lambda _, c: step(*c), state)

        return run

    state = (s.u, s.v, s.p, jnp.asarray(0.0, jnp.float32),
             jnp.asarray(0, jnp.int32))
    state = k_steps(SETTLE)(state)
    float(state[3])  # scalar fence

    def timed(k):
        run = k_steps(k)
        float(run(state)[3])  # compile + warm
        best = float("inf")
        for _ in range(REPS):
            t0 = time.perf_counter()
            float(run(state)[3])
            best = min(best, time.perf_counter() - t0)
        return best

    ta = timed(1)
    kb = 1 + max(2, min(64, int(1.0 / max(ta, 1e-3))))
    tb = timed(kb)
    return max((tb - ta) / (kb - 1), 1e-9)


if __name__ == "__main__":
    from pampi_tpu.utils import telemetry, xlacache

    xlacache.enable()  # per-solver 4096² builds become disk loads
    solvers = sys.argv[1:] or ["sor", "fft", "mg"]
    telemetry.start_run(tool="perf_ns2d4096", solvers=solvers)
    print(f"backend={jax.default_backend()} N={N} itermax=100 eps=1e-3 f32")
    for sv in solvers:
        ms = measure(sv) * 1e3
        telemetry.emit_span(f"ns2d4096.step[{sv}]", ms,
                            grid=[N, N], itermax=100,
                            protocol="chained-step two-point differencing")
        print(f"{sv:4s}: {ms:8.2f} ms/step")
