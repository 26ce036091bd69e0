"""Distributed obstacle-MG at scale: the committed-artifact measurement
(VERDICT r4 items 1 + 6).

Round 4 measured the one-shard 2048x512 distributed obstacle-MG at 4.26
ms/step (vs 1.55 single-device) but committed no artifact; round 5 moves
the dist smoothing onto the per-shard Pallas kernel
(ops/multigrid._pallas_dist_smoother_2d) and this tool records the result.

Protocol: production `_chunk_sm` (64 steps per
dispatch), warm-compiled, settled one chunk, then CHAINED-CHUNK two-point
differencing — time 1 chunk and k chunks from the same settled state,
per-step = (t_k - t_1) / (steps_k - steps_1), scalar-readback fences only.
Comparators measured in the SAME session: single-device obstacle-MG
(tools/perf_obstacle_mg.py protocol) and the capped dist SOR smoother.

Run on the real chip:  python tools/perf_obsdist_mg.py
Writes results/obsdist_mg2048.json.
"""

import json
import os
import sys
import time

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

import jax
import jax.numpy as jnp

from pampi_tpu.utils.params import read_parameter

REPS = 5
PAR = os.path.join(REPO, "configs", "canal_obstacle2048.par")


def measure_dist_step_ms(solver: str, dims=(1, 1)) -> dict:
    from pampi_tpu.models.ns2d_dist import NS2DDistSolver
    from pampi_tpu.parallel.comm import CartComm
    from pampi_tpu.utils import dispatch

    param = read_parameter(PAR).replace(
        tpu_dtype="float32", tpu_solver=solver,
        tpu_mesh=f"{dims[0]}x{dims[1]}",
    )
    comm = CartComm(ndims=2, dims=dims)
    before = dispatch.snapshot()  # the record is process-global
    s = NS2DDistSolver(param, comm, dtype=jnp.float32)
    # warm compile + settle one chunk (64 steps); initial_state matches
    # the chunk's arity (telemetry appends the in-band metrics vector)
    state = s._chunk_sm(*s.initial_state())
    float(state[3])

    def run_chunks(k):
        st = state
        for _ in range(k):
            st = s._chunk_sm(*st)
        float(st[3])  # scalar fence (no bulk device-to-host transfer)
        return int(st[4])

    def timed(k):
        nt_end = run_chunks(k)  # warm this chain length
        best = float("inf")
        for _ in range(REPS):
            t_start = time.perf_counter()
            run_chunks(k)
            best = min(best, time.perf_counter() - t_start)
        return best, nt_end

    ta, nta = timed(1)
    tb, ntb = timed(4)
    steps = ntb - nta
    ms = max(tb - ta, 1e-9) / steps * 1e3
    return {
        "ms_per_step": round(ms, 3),
        # only the records THIS solver build wrote (stale keys from earlier
        # measurements in the same process would misattribute)
        "dispatch": {k: v for k, v in dispatch.snapshot().items()
                     if before.get(k) != v},
        "steps_differenced": steps,
    }


def _with_jnp_smoothing(fn, *args, **kw):
    """Run a measurement with the Pallas MG smoothers ablated (every level
    falls back to the jnp sweeps) — the pallas-vs-jnp smoothing ablation,
    reproducible in-tool."""
    import pampi_tpu.ops.multigrid as mg

    saved = mg._PALLAS_SMOOTH_MIN_CELLS
    mg._PALLAS_SMOOTH_MIN_CELLS = 1 << 60
    try:
        return fn(*args, **kw)
    finally:
        mg._PALLAS_SMOOTH_MIN_CELLS = saved


if __name__ == "__main__":
    from tools.perf_obstacle_mg import measure_step_ms as single_ms

    rec = {
        "artifact": "obsdist_mg2048",
        "config": "configs/canal_obstacle2048.par at f32 (2048x512, "
                  "obstacle 3.0,1.5->4.0,2.5, eps=1e-5, itermax=500), "
                  "one shard of a (1,1) mesh",
        "protocol": "production _chunk_sm (64 steps/dispatch), warm+settled "
                    "1 chunk, chained-chunk two-point differencing (1 vs 4 "
                    "chunks), best-of-%d, scalar fences" % REPS,
        "backend": jax.default_backend(),
    }
    rec["dist_mg"] = measure_dist_step_ms("mg")
    rec["dist_mg_jnp_smoothing"] = _with_jnp_smoothing(
        measure_dist_step_ms, "mg"
    )
    rec["dist_sor_capped"] = measure_dist_step_ms("sor")
    rec["single_mg_ms_per_step"] = round(single_ms("mg"), 3)
    rec["single_mg_jnp_smoothing_ms_per_step"] = round(
        _with_jnp_smoothing(single_ms, "mg"), 3
    )
    from tools._artifact import write_merged

    write_merged(os.path.join(REPO, "results", "obsdist_mg2048.json"), rec)
