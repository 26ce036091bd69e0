"""Fused V-cycle (ISSUE 16, ops/mg_fused.py + the tpu_mg_fused knob):
the two-launch DOWN/UP Pallas cycle must converge to the SAME iterate as
the per-level jnp ladder it replaces (2-D/3-D × plain/obstacle), refuse
ragged single-level plans WITH a recorded reason, leave the knob-off
path bitwise-identical to the historical build, serve the fleet class
lane as a one-launch cycle, and — distributed — aggregate below-floor
bottoms into a replicated mini-V-cycle whose gathers carry the declared
`mg_aggregate.*` scope (commcheck's only RULE_RESHARD exemption).

Tier-1 carries one cheap representative per axis (2-D plain/obstacle
parity, the dist aggregation census, the static/refusal pins) to hold
its 870 s window; the 3-D, class-lane and FFT-coarse twins are
slow-marked — `make mg-suite` runs the complete matrix, and `make
mg-smoke` re-proves 2-D/3-D × plain/obstacle parity end-to-end."""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from pampi_tpu.analysis.jaxprcheck import count_prim
from pampi_tpu.ops import multigrid as mg
from pampi_tpu.utils import dispatch as disp

DT = jnp.float32

# both paths run the identical red-black ω=1 arithmetic, but the fused
# kernel evaluates full planes with masked-out dead cells, so f32
# summation order differs at the ulp scale
TOL = 2e-5


def _rhs2d(n, seed=0):
    rng = np.random.default_rng(seed)
    return jnp.zeros((n + 2, n + 2), DT).at[1:-1, 1:-1].set(
        jnp.asarray(rng.standard_normal((n, n)), DT))


def _assert_fused_matches_ladder(tag, key, make_pair, p0, rhs):
    """Build the off/on pair, pin the dispatch record + 2-launch trace,
    and assert same-cycle-count ulp-scale parity."""
    s_off = jax.jit(make_pair("off"))
    fn_on = make_pair("on")
    rec = disp.last(key) or ""
    assert rec.startswith("pallas_fused_cycle"), (tag, rec)
    assert "launches=2" in rec, (tag, rec)
    n_launch = count_prim(jax.make_jaxpr(fn_on)(p0, rhs).jaxpr,
                          "pallas_call")
    assert n_launch == 2, (tag, n_launch, rec)
    a, b = s_off(p0, rhs), jax.jit(fn_on)(p0, rhs)
    assert int(a[2]) == int(b[2]), (tag, int(a[2]), int(b[2]))
    d = float(jnp.max(jnp.abs(a[0] - b[0])))
    scale = max(float(jnp.max(jnp.abs(a[0]))), 1.0)
    assert d <= TOL * scale, (tag, d, scale)


def test_fused_cycle_matches_ladder_2d(monkeypatch):
    # shrink the DCT budget so 32² builds a real 2-level plan (at the
    # default budget the grid is single-level -> a vacuous refusal)
    monkeypatch.setattr(mg, "_DCT_BOTTOM_MAX_CELLS", 64)
    n = 32
    h = 1.0 / n
    rhs = _rhs2d(n)
    _assert_fused_matches_ladder(
        "plain2d", "mg2d_fused",
        lambda fused: mg.make_mg_solve_2d(
            n, n, h, h, 0.0, 3, DT, stall_rtol=0, fused=fused),
        jnp.zeros_like(rhs), rhs)


def test_fused_cycle_matches_ladder_2d_obstacle(monkeypatch):
    from pampi_tpu.ops.obstacle import make_masks

    monkeypatch.setattr(mg, "_DENSE_BOTTOM_MAX_CELLS", 64)
    n = 32
    h = 1.0 / n
    fluid = np.ones((n + 2, n + 2), bool)
    fluid[10:18, 12:22] = False
    m = make_masks(fluid, h, h, 1.7, DT)
    rhs = _rhs2d(n)
    _assert_fused_matches_ladder(
        "obs2d", "mg2d_obstacle_fused",
        lambda fused: mg.make_obstacle_mg_solve_2d(
            n, n, h, h, 0.0, 3, m, DT, stall_rtol=0, fused=fused),
        jnp.zeros_like(rhs), rhs)


@pytest.mark.slow
def test_fused_cycle_matches_ladder_3d(monkeypatch):
    monkeypatch.setattr(mg, "_DCT_BOTTOM_MAX_CELLS", 512)
    n = 16
    h = 1.0 / n
    rng = np.random.default_rng(1)
    rhs = jnp.zeros((n + 2,) * 3, DT).at[1:-1, 1:-1, 1:-1].set(
        jnp.asarray(rng.standard_normal((n, n, n)), DT))
    _assert_fused_matches_ladder(
        "plain3d", "mg3d_fused",
        lambda fused: mg.make_mg_solve_3d(
            n, n, n, h, h, h, 0.0, 3, DT, stall_rtol=0, fused=fused),
        jnp.zeros_like(rhs), rhs)


@pytest.mark.slow
def test_fused_cycle_matches_ladder_3d_obstacle(monkeypatch):
    from pampi_tpu.ops.obstacle3d import make_masks_3d

    monkeypatch.setattr(mg, "_DENSE_BOTTOM_MAX_CELLS", 512)
    n = 16
    h = 1.0 / n
    fl3 = np.ones((n + 2,) * 3, bool)
    fl3[6:10, 5:9, 7:12] = False
    m3 = make_masks_3d(fl3, h, h, h, 1.7, DT)
    rng = np.random.default_rng(2)
    rhs = jnp.zeros((n + 2,) * 3, DT).at[1:-1, 1:-1, 1:-1].set(
        jnp.asarray(rng.standard_normal((n, n, n)), DT))
    _assert_fused_matches_ladder(
        "obs3d", "mg3d_obstacle_fused",
        lambda fused: mg.make_obstacle_mg_solve_3d(
            n, n, n, h, h, h, 0.0, 3, m3, DT, stall_rtol=0, fused=fused),
        jnp.zeros_like(rhs), rhs)


def test_knob_off_is_the_historical_program():
    """fused="off" (and the default) must not merely be numerically
    close to the pre-ISSUE-16 ladder — it must trace to the IDENTICAL
    program (the knob is purely additive)."""
    n = 64
    h = 1.0 / n
    rhs = _rhs2d(n)
    p0 = jnp.zeros_like(rhs)
    default = mg.make_mg_solve_2d(n, n, h, h, 0.0, 3, DT, stall_rtol=0)
    off = mg.make_mg_solve_2d(n, n, h, h, 0.0, 3, DT, stall_rtol=0,
                              fused="off")
    assert str(jax.make_jaxpr(default)(p0, rhs)) == \
        str(jax.make_jaxpr(off)(p0, rhs))


def test_tpu_auto_keeps_ladder_with_blocker(monkeypatch):
    """The fused cycle does not lower for a TPU (ops/mg_fused.TPU_BLOCKER;
    tests/test_chip_compile.py pins the refusal): `auto` on a TPU keeps
    the ladder and says why, without calling the probe."""
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")

    def probe():
        raise AssertionError("the blocked family must not be probed")

    assert not disp.resolve_mg_fused("auto", "auto", jnp.float32,
                                     "mg2d_fused", probe=probe)
    assert disp.last("mg2d_fused").startswith("jnp (fused cycle not")


def test_ragged_single_level_refuses_with_reason():
    """A 33² grid is a single-level plan: the knob forced on must fall
    back to the jnp ladder AND say why in the dispatch record."""
    mg.make_mg_solve_2d(33, 33, 1 / 33, 1 / 33, 0.0, 2, DT,
                        stall_rtol=0, fused="on")
    reason = disp.last("mg2d_fused") or ""
    assert reason.startswith("jnp"), reason
    assert "single-level" in reason, reason


def test_expected_launches_derives_from_mg_record():
    """jaxprcheck's budget derivation reads the launch census verbatim
    from the fused-cycle dispatch record ("launches=N")."""
    from pampi_tpu.analysis.jaxprcheck import ChunkConfig, expected_launches

    cfg = ChunkConfig(name="x", family="ns2d", params={}, derive=True,
                      phases_key="ns2d_phases", mg_key="mg2d_fused")
    n, how = expected_launches(cfg, {
        "ns2d_phases": "jnp",
        "mg2d_fused": "pallas_fused_cycle (launches=2, levels=3)"})
    assert (n, how) == (2, "derived")
    n2, _ = expected_launches(cfg, {
        "ns2d_phases": "jnp",
        "mg2d_fused": "jnp_ladder (single-level plan)"})
    assert n2 == 0


# ---------------------------------------------------------------------
# fleet class lane (satellite 1): the one-launch class cycle serves the
# shape-class batcher; eligibility names the knob
# ---------------------------------------------------------------------

_B = dict(name="dcavity", imax=12, jmax=12, re=10.0, te=0.03, tau=0.5,
          itermax=8, eps=1e-4, omg=1.7, gamma=0.9, tpu_mesh="1",
          tpu_fuse_phases="off", tpu_solver="mg", tpu_mg_fused="on")


def _class_run(ic):
    from pampi_tpu import fleet
    from pampi_tpu.fleet.shapeclass import ClassSolver
    from pampi_tpu.utils.params import Parameter

    p = Parameter(**_B)
    tpl = ClassSolver(p, ic=ic, jc=ic)
    assert tpl._uses_pallas()
    rec = disp.last("mg_class_fused") or ""
    assert rec.startswith("pallas_class_cycle"), rec
    assert "launches=1" in rec, rec
    batched = fleet.BatchedSolver(tpl, [p], ["a"], family="ns2d_class")
    res = batched.results(batched.run())[0]
    assert not res["diverged"]
    return res


def test_class_eligibility_names_the_knob():
    from pampi_tpu.fleet import shapeclass as sc
    from pampi_tpu.utils.params import Parameter

    p = Parameter(**_B)
    assert sc.class_eligible(p) is None
    assert "tpu_mg_fused off" in sc.class_eligible(
        p.replace(tpu_mg_fused="off"))


@pytest.mark.slow
def test_class_mg_lane_matches_solo():
    """The class-cycle lane must converge to the solo mg solution: u/v
    at f32-accumulation scale; p mean-removed (the in-kernel smoothed
    bottom is a different coarse solver than the solo DCT bottom, so
    the pressure gauge differs — the CONTRACT deviation README
    documents)."""
    from pampi_tpu.models.ns2d import NS2DSolver
    from pampi_tpu.utils.params import Parameter

    p = Parameter(**_B)
    res = _class_run(16)
    solo = NS2DSolver(p)
    solo.run(progress=False)
    assert res["nt"] == solo.nt
    for name, a in zip("uvp", res["fields"]):
        ref = np.asarray(getattr(solo, name))
        if name == "p":
            a, ref = a - a.mean(), ref - ref.mean()
            tol = 0.05
        else:
            tol = 1e-5
        assert np.abs(a - ref).max() < tol, name


@pytest.mark.slow
def test_class_mg_lane_rung_invariant():
    """Padding invariance: the 16- and 32-cell class rungs run the
    identical per-lane arithmetic on different pads — bitwise equal."""
    f16 = _class_run(16)["fields"]
    f32 = _class_run(32)["fields"]
    for name, a, b in zip("uvp", f16, f32):
        assert np.abs(a - b).max() == 0.0, name


# ---------------------------------------------------------------------
# distributed bottoms (tentpole parts 2+3): coarse-level aggregation
# below the shard floor; FFT-preconditioned coarse for over-budget
# obstacle bottoms
# ---------------------------------------------------------------------


def _shard_solve(comm, solve, p0, rhs):
    from jax.sharding import PartitionSpec as P

    from pampi_tpu.parallel.comm import halo_exchange

    def kern(p_int, rhs_int):
        pe = halo_exchange(jnp.pad(p_int, 1), comm)
        re = halo_exchange(jnp.pad(rhs_int, 1), comm)
        p, res, it = solve(pe, re)
        return p[1:-1, 1:-1], res, it

    spec = P("j", "i")
    f = jax.jit(comm.shard_map(kern, in_specs=(spec, spec),
                               out_specs=(spec, P(), P()),
                               check_vma=False))
    p_out, res, it = f(p0[1:-1, 1:-1], rhs[1:-1, 1:-1])
    return f, np.asarray(p_out), float(res), int(it)


def test_dist_coarse_aggregation_matches_ladder(monkeypatch):
    """With the local ladder's bottom over the (shrunk) budget, the
    fused knob aggregates the gathered bottom into a replicated
    mini-V-cycle — recorded, and converging to the jnp-ladder iterate
    (mean-removed: the replicated bottom solve fixes a different
    gauge)."""
    from pampi_tpu.parallel.comm import CartComm

    monkeypatch.setattr(mg, "_DCT_BOTTOM_MAX_CELLS", 128)
    jmax = imax = 64
    dx = dy = 1.0 / imax
    dims = (2, 4)
    comm = CartComm(ndims=2, dims=dims)
    jl, il = jmax // dims[0], imax // dims[1]
    rng = np.random.default_rng(8)
    r = rng.standard_normal((jmax, imax))
    r -= r.mean()
    rhs = jnp.zeros((jmax + 2, imax + 2), DT).at[1:-1, 1:-1].set(
        jnp.asarray(r, DT))
    p0 = jnp.zeros_like(rhs)

    outs = {}
    traced = {}
    for knob in ("off", "on"):
        solve, _used = mg.make_dist_mg_solve_2d(
            comm, imax, jmax, jl, il, dx, dy, 1e-8, 30, DT, fused=knob)
        f, p_out, res, it = _shard_solve(comm, solve, p0, rhs)
        outs[knob] = p_out
        traced[knob] = jax.make_jaxpr(f)(p0[1:-1, 1:-1],
                                         rhs[1:-1, 1:-1]).jaxpr
    agg = disp.last("mg_dist_agg") or ""
    assert agg.startswith("replicated_vcycle"), agg
    assert disp.last("mg_dist_fused"), "the fused-refusal reason must land"

    a = outs["off"] - outs["off"].mean()
    b = outs["on"] - outs["on"].mean()
    assert np.abs(a - b).max() <= 1e-4 * np.abs(a).max()

    # the commcheck exemption (satellite 2): every all_gather of BOTH
    # builds (the ladder's replicated bottom solve gathers through the
    # same site) sits under the declared mg_aggregate.* scope, so the
    # RULE_RESHARD subtraction zeroes out — an unscoped gather would
    # leave a remainder and trip the ban
    from pampi_tpu.analysis.commcheck import aggregation_gathers, census

    for knob, jx in traced.items():
        declared = aggregation_gathers(jx)
        assert declared, (knob, "gathers must carry the named scope")
        assert set(declared) == {"mg_aggregate.gather2d"}, (knob, declared)
        assert sum(declared.values()) == \
            census(jx)["collectives"]["all_gather"], knob


@pytest.mark.slow
def test_dist_obstacle_fft_coarse(monkeypatch):
    """An over-budget obstacle bottom cannot be factorized dense: the
    knob routes the coarse correction through the FFT-preconditioned
    Richardson application — recorded, and not wrecking convergence."""
    from pampi_tpu.ops import obstacle as obst
    from pampi_tpu.parallel.comm import CartComm

    monkeypatch.setattr(mg, "_DENSE_BOTTOM_MAX_CELLS", 64)
    jmax, imax = 32, 64
    dx, dy = 4.0 / imax, 2.0 / jmax
    fluid = obst.build_fluid(imax, jmax, dx, dy, "1.2,0.5,2.0,1.1")
    m = obst.make_masks(fluid, dx, dy, 1.0, DT)
    dims = (2, 4)
    comm = CartComm(ndims=2, dims=dims)
    jl, il = jmax // dims[0], imax // dims[1]
    rng = np.random.default_rng(7)
    p0 = jnp.asarray(rng.standard_normal((jmax + 2, imax + 2)), DT)
    rhs = jnp.asarray(rng.standard_normal((jmax + 2, imax + 2)), DT)

    res = {}
    for knob in ("off", "on"):
        solve, _used = mg.make_dist_obstacle_mg_solve_2d(
            comm, imax, jmax, jl, il, dx, dy, 1e-8, 30, m, DT,
            fused=knob)
        _f, _p, res[knob], _it = _shard_solve(comm, solve, p0, rhs)
    coarse = disp.last("mg_dist_obstacle_coarse") or ""
    assert coarse.startswith("fft_richardson"), coarse
    assert res["on"] <= res["off"] * 4 + 1e-6, \
        "fft coarse must not wreck convergence"
