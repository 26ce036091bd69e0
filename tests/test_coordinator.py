"""Coordinated multi-host fault handling (parallel/coordinator.py, PR 10
tentpole) on the VIRTUAL-RANK simulation path: N full solver instances
driven in lockstep through the same agree-then-act protocol the real
multi-process allgather transport runs, so the global decisions — shared
transient budget, agreed rollback generation, checkpoint vote, abort —
are tier-1-provable on this CPU container. tests/test_multihost.py holds
the real cross-process acceptance cases (capability-gated, un-gate on
TPU/GPU or a gloo jaxlib).

Compile cost: every solver is 16², tpu_chunk=2, a handful of steps (the
test_faultinject sizing lever); the 4-rank cases pay 4 small builds by
design — that IS the simulated fleet.
"""

import json
import warnings

import numpy as np
import pytest

from pampi_tpu.models.ns2d import NS2DSolver
from pampi_tpu.parallel import coordinator as co
from pampi_tpu.utils import faultinject as fi
from pampi_tpu.utils import telemetry as tm
from pampi_tpu.utils.params import Parameter

_BASE = dict(name="dcavity", imax=16, jmax=16, re=10.0, te=0.05, tau=0.5,
             itermax=50, eps=1e-4, omg=1.7, gamma=0.9)


@pytest.fixture()
def tel_on(tmp_path, monkeypatch):
    path = tmp_path / "run.jsonl"
    monkeypatch.setenv("PAMPI_TELEMETRY", str(path))
    tm.reset()
    yield path
    tm.reset()


def _records(path, kind=None):
    recs = [json.loads(ln) for ln in open(path) if ln.strip()]
    return recs if kind is None else [r for r in recs if r["kind"] == kind]


def _fleet(n, param=None, **loop_kw):
    """n virtual ranks: each a full NS2DSolver built under its
    rank_scope (so @rank<R> clauses arm only their target), wrapped in a
    CoordinatedLoop mirroring the run() wiring."""
    param = param or Parameter(tpu_chunk=2, **_BASE)
    solvers, loops = [], []
    for r in range(n):
        with fi.rank_scope(r):
            solvers.append(NS2DSolver(param))
    for r, s in enumerate(solvers):
        loops.append(co.sim_rank_loop(s, "ns2d", 3, r, **loop_kw))
    return solvers, loops


def _quiet_run(loops):
    with warnings.catch_warnings():
        warnings.simplefilter("ignore")
        return co.LockstepSim(loops).run()


# ---------------------------------------------------------------------------
# the merge rule + the seam itself
# ---------------------------------------------------------------------------

def test_merge_words_semantics():
    """done = min (ALL ranks must finish), faults/divergence/vote = max
    (any rank's fault is everyone's), rollback target = min (the
    shallowest common generation)."""
    a = co.blank_word()
    a[co.W_DONE] = 1
    b = co.blank_word()
    b[co.W_FAULT] = 1
    b[co.W_DIVERGED] = 1
    b[co.W_ROLLBACK_NT] = 8
    c = co.blank_word()
    c[co.W_DONE] = 1
    c[co.W_ROLLBACK_NT] = 4
    c[co.W_CKPT] = 1
    m = co.merge_words(np.stack([a, b, c]))
    assert m[co.W_DONE] == 0          # b is not done
    assert m[co.W_FAULT] == 1
    assert m[co.W_DIVERGED] == 1
    assert m[co.W_ROLLBACK_NT] == 4   # the common (shallowest) generation
    assert m[co.W_CKPT] == 1
    # a lone clean word merges to itself (the SoloCoordinator identity)
    clean = co.blank_word()
    np.testing.assert_array_equal(co.merge_words(clean), clean)


def test_solo_coordinator_is_bitwise_identical():
    """tpu_coord on under one process: the protocol path (1-rank
    coordinator) must reproduce the historical uncoordinated run
    BITWISE — same compiled chunk, same confirmations, no trace change
    (the coordinator is host-side only)."""
    ref = NS2DSolver(Parameter(tpu_chunk=2, **_BASE))
    ref.run(progress=False)
    s = NS2DSolver(Parameter(tpu_chunk=2, tpu_coord="on", **_BASE))
    s.run(progress=False)
    assert s.nt == ref.nt and s.t == ref.t
    np.testing.assert_array_equal(np.asarray(s.u), np.asarray(ref.u))
    np.testing.assert_array_equal(np.asarray(s.v), np.asarray(ref.v))
    np.testing.assert_array_equal(np.asarray(s.p), np.asarray(ref.p))
    from pampi_tpu.utils import dispatch

    assert dispatch.last("coord_ns2d") == "coordinated (forced, 1 process)"


def test_coord_knob_validation():
    s = NS2DSolver(Parameter(tpu_chunk=2, tpu_coord="bogus", **_BASE))
    with pytest.raises(ValueError, match="tpu_coord"):
        s.run(progress=False)


def test_auto_is_uncoordinated_single_process():
    """The default leaves single-process runs on the exact historical
    loop: make_coordinator returns None and records why."""
    assert co.make_coordinator(Parameter(**_BASE), "ns2d") is None
    from pampi_tpu.utils import dispatch

    assert dispatch.last("coord_ns2d") == "uncoordinated (single process)"
    assert not co.coord_armed(Parameter(**_BASE))
    assert co.coord_armed(Parameter(tpu_coord="on", **_BASE))


# ---------------------------------------------------------------------------
# the fault-suite smoke: 4 simulated ranks, rank-2 transient + rank-0
# divergence rollback — identical post-recovery state on every rank
# ---------------------------------------------------------------------------

def test_four_rank_transient_retried_globally(faults, tel_on):
    """An injected rank-LOCAL transient (rank 2, chunk 2) is agreed at
    the boundary and the chunk re-dispatched on EVERY rank: all four
    finals match the uninjected solo run bitwise (same compiled chunk,
    same inputs), and the decision is one flight-recorder `coord`
    line."""
    ref = NS2DSolver(Parameter(tpu_chunk=2, **_BASE))
    ref.run(progress=False)
    faults("transient@chunk2@rank2")
    solvers, loops = _fleet(4)
    _quiet_run(loops)
    for r, s in enumerate(solvers):
        assert s.nt == ref.nt, f"rank {r}"
        np.testing.assert_array_equal(np.asarray(s.u), np.asarray(ref.u))
        np.testing.assert_array_equal(np.asarray(s.p), np.asarray(ref.p))
    retries = [r for r in _records(tel_on, "coord")
               if r["event"] == "retry"]
    assert len(retries) == 1  # one GLOBAL decision, one line (rank 0)
    assert retries[0]["budget_left"] == 0


def test_four_rank_divergence_rolls_every_rank_back(faults, tel_on):
    """A rank-0-only corruption diverges rank 0; the merged word rolls
    EVERY rank back to the same agreed generation and every rank
    re-drives with the same clamped dt — post-recovery state identical
    on all ranks, finite, past te. The fault-suite coordinator smoke."""
    faults("nan@step5:u@rank0")
    solvers, loops = _fleet(
        4, Parameter(tpu_chunk=2, tpu_recover_ring=4, **_BASE))
    _quiet_run(loops)
    ref = solvers[0]
    assert ref.t > _BASE["te"]
    for r, s in enumerate(solvers):
        assert np.isfinite(np.asarray(s.u)).all(), f"rank {r}"
        assert s._dt_scale == 0.5, f"rank {r}"  # ONE agreed clamp each
        assert s.nt == ref.nt and s.t == ref.t, f"rank {r}"
        np.testing.assert_array_equal(np.asarray(s.u), np.asarray(ref.u))
        np.testing.assert_array_equal(np.asarray(s.p), np.asarray(ref.p))
    rolls = [r for r in _records(tel_on, "coord")
             if r["event"] == "rollback"]
    assert len(rolls) == 1
    assert rolls[0]["target_nt"] == 4  # the boundary before the bad step


def test_global_budget_spans_ranks_and_aborts_everywhere(faults):
    """The budget is GLOBAL: back-to-back transients on DIFFERENT ranks
    inside one replenish window exhaust the single shared charge, and
    the agreed decision is a clean abort on every rank — never one rank
    dying inside a collective."""
    faults("transient@chunk2@rank2,transient@chunk3@rank0")
    _solvers, loops = _fleet(
        4, Parameter(tpu_chunk=2, tpu_retry_replenish=50, **_BASE))
    with warnings.catch_warnings():
        warnings.simplefilter("ignore")
        with pytest.raises(co.CoordinatorAbort, match="budget exhausted"):
            co.LockstepSim(loops).run()


def test_global_budget_replenishes_after_agreed_clean_chunks(faults):
    """Spaced rank-local transients past the replenish window both
    retry (the shared budget refills on AGREED clean boundaries) and
    the fleet completes — the PR 4 replenish semantics, now global."""
    faults("transient@chunk2@rank1,transient@chunk6@rank3")
    solvers, loops = _fleet(
        4, Parameter(tpu_chunk=1, tpu_retry_replenish=3, **_BASE),
        replenish_after=3)
    _quiet_run(loops)
    for s in solvers:
        assert s.t > _BASE["te"]
        assert np.isfinite(np.asarray(s.u)).all()


def test_checkpoint_vote_commits_on_every_rank(faults, tel_on):
    """The agreed checkpoint vote: every rank's on_ckpt commit fires at
    the SAME boundaries (the manifest write itself is rank-0-gated in
    production; the agreement is what this pins), and each commit is a
    `coord` ckpt line."""
    commits = {r: [] for r in range(3)}
    solvers, loops = _fleet(3, Parameter(tpu_chunk=2, **_BASE))
    for r, loop in enumerate(loops):
        loop.ckpt_every = 2
        loop.on_ckpt = lambda s, r=r: commits[r].append(
            int(s[4]))  # nt at the commit point
    _quiet_run(loops)
    assert commits[0]  # the cadence fired at least once
    assert commits[0] == commits[1] == commits[2]  # same agreed boundaries
    votes = [r for r in _records(tel_on, "coord") if r["event"] == "ckpt"]
    assert len(votes) == len(commits[0])


def test_abort_on_unreplenished_budget_is_loud_not_divergent(faults):
    """tpu_coord off under one process keeps the historical path even
    with rank clauses armed (targeting rank 0 = this process): the
    uncoordinated loop's own budget handles it."""
    faults("transient@chunk2@rank0")
    s = NS2DSolver(Parameter(tpu_chunk=2, tpu_coord="off", **_BASE))
    with pytest.warns(UserWarning, match="transient"):
        s.run(progress=False)
    assert s.t > _BASE["te"]


def test_coordinated_pallas_fallback_completes(faults, tel_on):
    """The W_FALLBACK decision through the production seam: an injected
    pallas failure under the 1-rank coordinator swaps to the jnp chunk
    via the agreed word (retry() on the failing rank, mirrored on
    peers) and the run completes — one `coord` fallback line."""
    faults("pallas@chunk2")
    s = NS2DSolver(Parameter(tpu_fuse_phases="on", tpu_solver="fft",
                             tpu_chunk=2, tpu_coord="on", **_BASE))
    assert s._uses_pallas()
    with warnings.catch_warnings():
        warnings.simplefilter("ignore")
        s.run(progress=False)
    assert s._backend == "jnp" and s.t > _BASE["te"]
    assert np.isfinite(np.asarray(s.u)).all()
    falls = [r for r in _records(tel_on, "coord")
             if r["event"] == "fallback"]
    assert len(falls) == 1


# ---------------------------------------------------------------------------
# xlacache wedge hardening (satellite): dead cache path -> warn + uncached
# ---------------------------------------------------------------------------

@pytest.fixture
def cache_switch_restored():
    """The probe-failure path switches JAX's cache off process-wide."""
    import jax

    prev = jax.config.jax_enable_compilation_cache
    yield
    jax.config.update("jax_enable_compilation_cache", prev)


def test_xlacache_unusable_dir_proceeds_uncached(tmp_path, monkeypatch,
                                                 tel_on,
                                                 cache_switch_restored):
    """A cache path that cannot be used (here: a FILE where the dir
    should be) degrades to warn-and-run-uncached with a structured
    telemetry `warning` record — never a blocked run."""
    from pampi_tpu.utils import xlacache

    bogus = tmp_path / "cachefile"
    bogus.write_text("not a directory")
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(bogus))
    with pytest.warns(UserWarning, match="UNCACHED"):
        assert xlacache.enable() is None
    warns = _records(tel_on, "warning")
    assert len(warns) == 1 and warns[0]["component"] == "xlacache"
    from tools import check_artifact as ca
    from tools import telemetry_report as tr

    summ = tr.summary(_records(tel_on))
    assert summ["warnings"][0]["component"] == "xlacache"
    assert ca.lint_telemetry_summary(summ, "X") == []


def test_xlacache_hung_probe_times_out(tmp_path, monkeypatch, tel_on,
                                       cache_switch_restored):
    """The documented wedge (xlacache.py): storage that HANGS (a dead
    shared mount — os calls block forever) is bounded by the probe
    timeout; the run proceeds uncached instead of wedging the fleet."""
    import time

    from pampi_tpu.utils import xlacache

    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path / "cache"))
    monkeypatch.setenv("PAMPI_XLA_CACHE_TIMEOUT", "0.2")
    monkeypatch.setattr(xlacache.os, "makedirs",
                        lambda *a, **k: time.sleep(5))
    with pytest.warns(UserWarning, match="UNCACHED"):
        assert xlacache.enable() is None
    warns = _records(tel_on, "warning")
    assert warns and "probe exceeded" in warns[0]["reason"]


# ---------------------------------------------------------------------------
# coord records through the report + artifact lint (schema v5)
# ---------------------------------------------------------------------------

def test_coord_records_render_and_lint(tel_on):
    tm.emit("coord", event="armed", family="ns2d_dist", mode="multihost",
            nranks=4, rank=0)
    tm.emit("coord", event="retry", boundary=3, family="ns2d_dist",
            budget_left=0, t=0.5)
    tm.emit("coord", event="rollback", boundary=7, family="ns2d_dist",
            target_nt=8, t=0.25)
    tm.emit("ckpt", event="elastic_save", path="ck", generation=2,
            mesh=[2, 4], t=0.5, nt=10, rotated=True)
    tm.emit("ckpt", event="elastic_load", path="ck", generation=2,
            mesh_now=[2, 2], t=0.5, nt=10)

    from tools import check_artifact as ca
    from tools import telemetry_report as tr

    recs = _records(tel_on)
    text = tr.render(recs)
    for needle in ("coordinator (agreed global decisions)",
                   "armed: multihost nranks=4", "retry", "rollback",
                   "elastic_save", "elastic_load"):
        assert needle in text, needle
    summ = tr.summary(recs)
    assert summ["coord"]["nranks"] == 4
    assert summ["coord"]["decisions"] == {"retry": 1, "rollback": 1}
    assert summ["ckpt"]["elastic_save"] == 1
    assert summ["ckpt"]["elastic_load"] == 1
    where = "BENCH.telemetry_summary"
    assert ca.lint_telemetry_summary(summ, where) == []
    # gutted blocks are FLAGGED, not waved through
    assert ca.lint_telemetry_summary({**summ, "coord": "zap"}, where)
    assert ca.lint_telemetry_summary({**summ, "coord": {}}, where)
    assert ca.lint_telemetry_summary(
        {**summ, "warnings": [{"reason": "no component"}]}, where)


def test_membership_records_render_and_lint(tel_on):
    """Schema v6: the dead/epoch/shrink kinds and the ckpt ledger events
    render in the coord section's membership subsection, summarize into
    coord.membership, and lint clean — while a legacy (pre-v6) summary
    without the membership key still passes, and a gutted membership
    block is flagged."""
    tm.emit("coord", event="armed", family="ns2d_dist", mode="multihost",
            nranks=2, rank=0)
    tm.emit("dead", ranks=[1], epoch=1, boundary=5, nranks=2,
            watchdog_s=5.0, family="ns2d_dist")
    tm.emit("epoch", epoch=1, nranks=1, survivors=[0])
    tm.emit("shrink", family="ns2d_dist", path="ck", survivors=1,
            generation=3, dead=[1], epoch=1, t=0.5, nt=10)
    tm.emit("ckpt", event="ledger_save", path="ck", generation=3,
            ledger={"budget_spent": 1, "epoch": 0})
    tm.emit("ckpt", event="ledger_restore", path="ck", rebuilt=True,
            ledger={"budget_spent": 1, "epoch": 0})

    from tools import check_artifact as ca
    from tools import telemetry_report as tr

    recs = _records(tel_on)
    # the membership kinds arrived in v6; later schema bumps
    # (v7: the serving plane) must keep rendering them
    assert recs[0]["v"] == tm.SCHEMA_VERSION >= 6
    text = tr.render(recs)
    for needle in ("membership (dead ranks / shrink epochs)",
                   "DEAD rank(s) [1]", "epoch 1: 1 survivor(s) [0]",
                   "shrink-resume [ns2d_dist] on 1 device(s) from "
                   "generation 3", "ledger_save", "ledger_restore"):
        assert needle in text, needle
    summ = tr.summary(recs)
    mem = summ["coord"]["membership"]
    assert mem["dead"][0]["ranks"] == [1]
    assert mem["epochs"][0]["survivors"] == [0]
    assert mem["shrinks"][0]["generation"] == 3
    assert summ["ckpt"]["ledger_save"] == 1
    assert summ["ckpt"]["ledger_restore"] == 1
    where = "BENCH.telemetry_summary"
    assert ca.lint_telemetry_summary(summ, where) == []
    # legacy summaries (no membership subsection) still pass
    legacy = {**summ, "coord": {"nranks": 2, "decisions": {"retry": 1}}}
    assert ca.lint_telemetry_summary(legacy, where) == []
    # gutted membership blocks are FLAGGED, not waved through
    for gutted in ("zap", {"dead": [{"no_ranks": 1}]},
                   {"epochs": "zap"}):
        bad = {**summ, "coord": {**summ["coord"], "membership": gutted}}
        assert ca.lint_telemetry_summary(bad, where), gutted


# ---------------------------------------------------------------------------
# PR 12: the dead-rank matrix — watchdog, membership agreement, shrink
# epoch, elastic shrink-resume, ledger persistence
# ---------------------------------------------------------------------------

def _warm(solvers):
    """Pre-compile each replica's chunk (one discarded functional call)
    so a small watchdog window judges DISPATCHES, not first-call
    compiles."""
    for s in solvers:
        out = s._chunk_fn(*s.initial_state())
        float(out[3])


def test_dead_rank_at_boundary_is_structured(faults, tel_on):
    """A rank that stops answering (dead@chunk3@rank1) is agreed DEAD by
    the survivor within one watchdog window: the same RankDeadError
    names the rank, the survivor set and the incremented shrink epoch,
    and the verdict is a flight-recorder `dead` + `epoch` pair — never a
    hang, never an anonymous timeout."""
    faults("dead@chunk3@rank1")
    _solvers, loops = _fleet(2)
    with warnings.catch_warnings():
        warnings.simplefilter("ignore")
        with pytest.raises(co.RankDeadError, match=r"DEAD rank\(s\) \[1\]"):
            co.LockstepSim(loops).run()
    dead = _records(tel_on, "dead")
    assert len(dead) == 1
    assert dead[0]["ranks"] == [1] and dead[0]["epoch"] == 1
    epochs = _records(tel_on, "epoch")
    assert len(epochs) == 1
    assert epochs[0]["survivors"] == [0] and epochs[0]["nranks"] == 1


def test_hang_past_watchdog_is_dead(faults, tel_on, monkeypatch):
    """Mid-dispatch death via hang: the rank never raises — it just
    stops coming back — and ONLY the watchdog can tell. With the hang
    armed past the window, the survivor's collection round times out on
    rank 1 and the membership round declares it dead, exactly like the
    stop-answering shape."""
    monkeypatch.setenv("PAMPI_FAULT_HANG_S", "30")
    faults("hang@chunk3@rank1")
    solvers, loops = _fleet(2)
    _warm(solvers)
    with warnings.catch_warnings():
        warnings.simplefilter("ignore")
        with pytest.raises(co.RankDeadError) as excinfo:
            co.LockstepSim(loops, watchdog=0.5).run()
    assert excinfo.value.ranks == [1]
    assert excinfo.value.survivors == [0]
    # the cancel broadcast bounds the abandoned sleeper: give it a beat
    # to unwind its rank_scope before the next test builds solvers
    import time

    time.sleep(0.2)


def test_double_death_names_both(faults, tel_on):
    """Two ranks dying in the same round: the OR-merged dead mask names
    BOTH, the survivors still agree one epoch — degraded-capacity
    accounting never undercounts the loss."""
    faults("dead@chunk3@rank1,dead@chunk3@rank2")
    _solvers, loops = _fleet(3)
    with warnings.catch_warnings():
        warnings.simplefilter("ignore")
        with pytest.raises(co.RankDeadError) as excinfo:
            co.LockstepSim(loops).run()
    assert excinfo.value.ranks == [1, 2]
    assert excinfo.value.survivors == [0]
    assert _records(tel_on, "dead")[0]["ranks"] == [1, 2]


def test_death_during_rollback_still_agreed(faults, tel_on):
    """Death AFTER an agreed divergence rollback: the fleet first rolls
    every rank back (the PR 10 protocol), then rank 1 dies on the
    re-drive — the survivor holds the rolled-back state and still gets
    the structured verdict. Protocol states compose; neither eats the
    other's record."""
    faults("nan@step3:u@rank0,dead@chunk5@rank1")
    solvers, loops = _fleet(
        2, Parameter(tpu_chunk=2, tpu_recover_ring=4, **_BASE))
    with warnings.catch_warnings():
        warnings.simplefilter("ignore")
        with pytest.raises(co.RankDeadError) as excinfo:
            co.LockstepSim(loops).run()
    assert excinfo.value.ranks == [1]
    rolls = [r for r in _records(tel_on, "coord")
             if r["event"] == "rollback"]
    assert len(rolls) == 1  # the rollback happened BEFORE the death
    assert _records(tel_on, "dead")[0]["epoch"] == 1
    # the survivor's confirmed state is the agreed rolled-back
    # trajectory: finite (the corruption was rolled away pre-death)
    assert np.isfinite(np.asarray(loops[0]._confirmed[0])).all()
    del solvers  # replicas only exist to anchor the loops


def test_dead_rank_shrink_resume_bitwise(faults, tel_on, tmp_path):
    """THE survival contract (ISSUE 12 acceptance): rank 1 dies at chunk
    5 of a 2-rank coordinated run with an agreed elastic checkpoint
    cadence; the survivor raises the structured verdict, shrink-resumes
    from the newest agreed generation onto one device, completes — and
    the final state is BITWISE-identical to a clean run restored from
    the same generation on the same shrunk capacity. The manifest also
    carries the fault ledger (the no-amnesia payload)."""
    from pampi_tpu.fleet.scheduler import shrink_resume
    from pampi_tpu.utils import checkpoint as ckpt

    manifest = str(tmp_path / "ck.elastic")
    faults("dead@chunk5@rank1")
    param = Parameter(tpu_chunk=2, tpu_checkpoint=manifest,
                      tpu_ckpt_elastic=1, **dict(_BASE, te=0.08))
    solvers, loops = [], []
    for r in range(2):
        with fi.rank_scope(r):
            solvers.append(NS2DSolver(param))
    for r, s in enumerate(solvers):
        loop = co.sim_rank_loop(s, "ns2d", 3, r, ckpt_every=2)
        if r == 0:
            def on_ckpt(state, ledger=None, s=s):
                s.u, s.v, s.p = state[0], state[1], state[2]
                s.t, s.nt = float(state[3]), int(state[4])
                ckpt.save_elastic(manifest, s, ledger=ledger)

            on_ckpt.takes_ledger = True
            loop.on_ckpt = on_ckpt
        loops.append(loop)
    with warnings.catch_warnings():
        warnings.simplefilter("ignore")
        with pytest.raises(co.RankDeadError) as excinfo:
            co.LockstepSim(loops).run()
    man = ckpt._read_manifest(manifest)
    assert "ledger" in man  # the agreed commit persisted protocol state
    gen = int(man["generation"])
    assert gen >= 1

    import jax

    shrunk = [jax.devices()[0]]
    resumed = shrink_resume(manifest, param, family="ns2d",
                            devices=shrunk, dead=excinfo.value.ranks,
                            epoch=excinfo.value.epoch)
    assert resumed.nt == man["nt"]  # the newest agreed generation
    with warnings.catch_warnings():
        warnings.simplefilter("ignore")
        resumed.run(progress=False)
    assert resumed.t > 0.08

    oracle = NS2DSolver(param)
    ckpt.load_elastic(manifest, oracle)
    with warnings.catch_warnings():
        warnings.simplefilter("ignore")
        oracle.run(progress=False)
    assert resumed.nt == oracle.nt and resumed.t == oracle.t
    np.testing.assert_array_equal(np.asarray(resumed.u),
                                  np.asarray(oracle.u))
    np.testing.assert_array_equal(np.asarray(resumed.v),
                                  np.asarray(oracle.v))
    np.testing.assert_array_equal(np.asarray(resumed.p),
                                  np.asarray(oracle.p))
    shrinks = _records(tel_on, "shrink")
    assert len(shrinks) == 1 and shrinks[0]["dead"] == [1]
    assert shrinks[0]["generation"] == gen


def test_cli_resume_after_death_policy(tmp_path):
    """The driver's dead-rank policy hook (cli._resume_after_death):
    armed (tpu_dead_resume 1 + elastic manifest on disk) it
    shrink-resumes onto this process's devices and completes the run;
    disarmed it surfaces the structured error and returns None (exit 3
    at the cli)."""
    from pampi_tpu import cli
    from pampi_tpu.utils import checkpoint as ckpt

    manifest = str(tmp_path / "ck.elastic")
    param = Parameter(tpu_chunk=2, tpu_checkpoint=manifest,
                      tpu_ckpt_elastic=1, **_BASE)
    donor = NS2DSolver(param)  # t=0: the resume drives the whole run
    ckpt.save_elastic(manifest, donor,
                      ledger={"budget_spent": 0, "epoch": 1})
    exc = co.RankDeadError(ranks=[1], epoch=1, boundary=3,
                           family="ns2d", survivors=[0])
    with warnings.catch_warnings():
        warnings.simplefilter("ignore")
        solver = cli._resume_after_death(param, exc, is3d=False)
    assert solver is not None
    assert solver.t > _BASE["te"]
    assert np.isfinite(np.asarray(solver.u)).all()

    assert cli._resume_after_death(
        param.replace(tpu_dead_resume=0), exc, is3d=False) is None
    assert cli._resume_after_death(
        param.replace(tpu_checkpoint=""), exc, is3d=False) is None


def test_ledger_keeps_pallas_broken_verdict(tmp_path):
    """No probation amnesia (ISSUE 12 acceptance): a manifest carrying a
    pallas-broken verdict parks the restored solver on the jnp path at
    load time, pallas_retry latches the dead verdict (no restore, ever),
    and the coordinated loop seeds the spent budget + shrink epoch —
    rank-symmetric because every rank reads the same manifest."""
    from pampi_tpu.models._driver import pallas_retry
    from pampi_tpu.utils import checkpoint as ckpt

    manifest = str(tmp_path / "ck.elastic")
    param = Parameter(tpu_fuse_phases="on", tpu_solver="fft",
                      tpu_chunk=2, **_BASE)
    donor = NS2DSolver(param)
    assert donor._uses_pallas()
    ledger = {"budget_spent": 1, "epoch": 2,
              "pallas": {"broken": True, "on_jnp": True,
                         "backend": "jnp"}}
    ckpt.save_elastic(manifest, donor, ledger=ledger)

    restored = NS2DSolver(param)
    assert restored._backend != "jnp"
    ckpt.load_elastic(manifest, restored)
    assert restored._fault_ledger["pallas"]["broken"] is True
    assert restored._backend == "jnp"  # parked on jnp at load
    hook = pallas_retry(restored, "pressure solve", restore_after=2)
    assert hook._dead  # the verdict survived the restart
    for _ in range(6):
        assert hook.on_clean_chunk() is None  # never restored
    loop = co.sim_rank_loop(restored, "ns2d", 3, 0)
    loop.retry = hook           # the production wiring carries the hook
    assert loop.epoch == 2      # the shrink epoch carried over
    assert loop._budget == 0    # spent charge carried over (of 1)
    assert loop.ledger()["pallas"]["broken"] is True  # round-trips


def test_short_run_end_of_run_manifest_keeps_ledger(tmp_path):
    """Regression (found driving the CLI): a coordinated run that
    completes BEFORE the first checkpoint-cadence boundary never fires
    on_ckpt, so without the completion stash the end-of-run elastic
    write dropped the ledger and `ckpt_fsck --survivors` declared a
    healthy manifest CORRUPT. The agreed-done ledger must reach the
    solver so save_elastic's _fault_ledger fallback persists it."""
    from pampi_tpu.utils import checkpoint as ckpt

    manifest = str(tmp_path / "ck.elastic")
    param = Parameter(tpu_coord="on", tpu_checkpoint=manifest,
                      tpu_ckpt_elastic=1, tpu_chunk=2,
                      tpu_ckpt_every=1000, **_BASE)
    s = NS2DSolver(param)
    s.run(progress=False)
    assert s._fault_ledger is not None  # stashed at loop completion
    ckpt.save_elastic(manifest, s)  # the cli's end-of-run write
    led = json.load(open(manifest)).get("ledger")
    assert led is not None and led["budget_spent"] == 0
    import subprocess
    import sys as _sys

    import tools.ckpt_fsck as fsck_mod

    r = subprocess.run([_sys.executable, fsck_mod.__file__,
                        "--survivors", "1", manifest],
                       capture_output=True, text=True)
    assert r.returncode == 0, r.stdout + r.stderr
    assert "survivors 1: ok" in r.stdout


def test_fallback_mirrors_onto_transient_rank(faults, tel_on):
    """Review regression: a rank that raised a TRANSIENT in the same
    round a peer took the pallas fallback must STILL mirror the swap —
    guarding on 'did I raise anything' would leave it on the pallas
    program and desynchronize the fleet. Rank 0 pallas-fails and rank 1
    transient-fails at the same boundary; both must end on jnp with
    identical state."""
    faults("pallas@chunk2@rank0,transient@chunk2@rank1")
    param = Parameter(tpu_fuse_phases="on", tpu_solver="fft",
                      tpu_chunk=2, **_BASE)
    solvers = []
    for r in range(2):
        with fi.rank_scope(r):
            solvers.append(NS2DSolver(param))
    loops = []
    for r, s in enumerate(solvers):
        from pampi_tpu.models._driver import pallas_retry

        loop = co.sim_rank_loop(s, "ns2d", 3, r)
        loop.retry = pallas_retry(s, "pressure solve")
        loops.append(loop)
    _quiet_run(loops)
    for r, s in enumerate(solvers):
        assert s._backend == "jnp", f"rank {r} kept the pallas program"
        assert s.t > _BASE["te"]
    assert solvers[0].nt == solvers[1].nt
    np.testing.assert_array_equal(np.asarray(solvers[0].u),
                                  np.asarray(solvers[1].u))
