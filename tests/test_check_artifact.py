"""tools/check_artifact.py: the committed BENCH/MULTICHIP artifacts must
lint clean (tier-1 — a driver round that writes a malformed artifact, or a
refactor that renames a decomposition field, fails here), and the lint
must actually catch violations."""

import glob
import os

from tools import check_artifact as ca

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def test_committed_artifacts_lint_clean():
    files = sorted(
        glob.glob(os.path.join(REPO, "BENCH_r*.json"))
        + glob.glob(os.path.join(REPO, "MULTICHIP_r*.json"))
    )
    assert files, "no committed artifacts found"
    errors = [e for path in files for e in ca.lint_file(path)]
    assert errors == []


def test_lint_catches_missing_required():
    assert any("rc" in e for e in ca.lint_bench({"n": 1}))
    assert any("ok" in e for e in ca.lint_multichip({"n_devices": 8}))


# the tools/_artifact.py normalized schema every artifact now carries
_NORM = {"schema_version": 1, "metrics": []}


def test_lint_normalized_schema():
    """schema_version + the machine-readable metrics list are required
    (the bench_trend input must never degrade back to tail scraping);
    malformed entries and non-cpu/tpu backend tags are flagged."""
    base = {"n": 1, "cmd": "x", "rc": 0, "tail": "", **_NORM}
    assert ca.lint_bench(base) == []
    assert any("schema_version" in e for e in ca.lint_bench(
        {"n": 1, "cmd": "x", "rc": 0, "tail": "", "metrics": []}))
    assert any("metrics" in e for e in ca.lint_bench(
        {"n": 1, "cmd": "x", "rc": 0, "tail": "", "schema_version": 1}))
    bad = dict(base, metrics=[{"name": "m", "value": 1.0,
                               "unit": "x", "backend": "gpu"}])
    assert any("cpu|tpu" in e for e in ca.lint_bench(bad))
    bad = dict(base, metrics=[{"name": "m"}])
    assert any("value" in e for e in ca.lint_bench(bad))


def test_lint_xprof_summary_block():
    base = {"n": 1, "cmd": "x", "rc": 0, "tail": "", **_NORM}
    good = dict(base, xprof_summary={
        "mode": "trace", "scopes": {}, "collectives": {},
        "exchange_device_ms": 1.0, "exchange_exposed_ms": 1.0})
    assert ca.lint_bench(good) == []
    wall = dict(base, xprof_summary={"mode": "wallclock", "wall_ms": 5.0})
    assert ca.lint_bench(wall) == []  # degraded mode carries less
    bad = dict(base, xprof_summary={"mode": "trace"})
    assert any("scopes" in e for e in ca.lint_bench(bad))


def test_lint_catches_gutted_decomposition():
    """An NS step line without the solve/non-solve decomposition keys is a
    schema violation — null VALUES are legal (off-TPU), missing KEYS are
    not."""
    good = {"n": 1, "cmd": "x", "rc": 0, "tail": "", **_NORM,
            "parsed_ns2d": {"metric": "ns2d_dcavity4096_ms_per_step",
                            "value": 1.0, "unit": "ms/step",
                            "solve_ms": None, "nonsolve_ms": None,
                            "phases": "jnp", "steps_timed": 8}}
    assert ca.lint_bench(good) == []
    bad = dict(good, parsed_ns2d={
        "metric": "ns2d_dcavity4096_ms_per_step", "value": 1.0,
        "unit": "ms/step"})
    assert any("solve_ms" in e for e in ca.lint_bench(bad))


def test_lint_catches_gutted_launch_census():
    """launches_per_step blocks must carry the K-fusion census keys
    (ISSUE 17) — a quotient with no dispatch record, raw count, or
    divisor cannot be audited; ns2d_small_ms_per_step rides the
    existing DECOMP_KEYS rule by its name shape."""
    good = {"n": 1, "cmd": "x", "rc": 0, "tail": "", **_NORM,
            "parsed_lps": {"metric": "launches_per_step", "value": 0.5,
                           "unit": "launches/step",
                           "chunk_fuse_dispatch": "scan (K=4)",
                           "pallas_calls": 2, "k": 4}}
    assert ca.lint_bench(good) == []
    bad = dict(good, parsed_lps={"metric": "launches_per_step",
                                 "value": 0.5, "unit": "launches/step"})
    assert any("chunk_fuse_dispatch" in e for e in ca.lint_bench(bad))
    small = dict(good, parsed_small={
        "metric": "ns2d_small_ms_per_step", "value": 0.4,
        "unit": "ms/step"})
    assert any("solve_ms" in e for e in ca.lint_bench(small))


def test_lint_telemetry_summary_block():
    base = {"n_devices": 8, "rc": 0, "ok": True, "skipped": False,
            "tail": "", **_NORM}
    good = dict(base, telemetry_summary={
        "schema_version": 1, "dispatch": {}, "records": 4,
        "chunks": {"count": 1, "steps": 8}})
    assert ca.lint_multichip(good) == []
    bad = dict(base, telemetry_summary={"records": 4})
    assert any("schema_version" in e for e in ca.lint_multichip(bad))


def test_lint_dispatch_snapshot_overlap_keys():
    """Once a dryrun snapshot records ANY overlap_* decision, BOTH dist
    families must carry an overlap/serial-tagged value; pre-overlap
    snapshots (and tails without one) pass unchanged."""
    ok_tail = ("OK ns2d-dist overlap mesh=(4, 2) [overlap (forced)]\n"
               "dispatch snapshot: {'overlap_ns2d_dist': 'overlap (forced)',"
               " 'overlap_ns3d_dist': 'serial (no TPU)'}\n")
    assert ca.lint_dispatch_snapshot(ok_tail, "M") == []
    # one family missing -> violation naming the key
    bad_tail = ("dispatch snapshot: {'overlap_ns2d_dist': "
                "'overlap (forced)'}\n")
    errs = ca.lint_dispatch_snapshot(bad_tail, "M")
    assert len(errs) == 1 and "overlap_ns3d_dist" in errs[0]
    # untagged value -> violation
    weird = ("dispatch snapshot: {'overlap_ns2d_dist': 'maybe', "
             "'overlap_ns3d_dist': 'overlap'}\n")
    errs = ca.lint_dispatch_snapshot(weird, "M")
    assert len(errs) == 1 and "overlap_ns2d_dist" in errs[0]
    # pre-overlap snapshot / no snapshot: pass
    assert ca.lint_dispatch_snapshot(
        "dispatch snapshot: {'ns2d_dist': 'jnp_ca'}\n", "M") == []
    assert ca.lint_dispatch_snapshot("no snapshot here\n", "M") == []
    # the committed r06 artifact carries both keys (the live subject)
    import json, os
    with open(os.path.join(ca.REPO, "MULTICHIP_r06.json")) as fh:
        d = json.load(fh)
    assert "overlap_ns2d_dist" in d["tail"] \
        and "overlap_ns3d_dist" in d["tail"]
    assert ca.lint_multichip(d, "MULTICHIP_r06") == []


def test_lint_autoscale_block():
    """The autopilot decision block (ISSUE 19): the decision tally, the
    ordered transition log and the final posture must all ride the
    block; a transition that cannot say what it decided is noise."""
    good = {"records": 25, "decisions": {"hold": 20, "grow": 1},
            "transitions": [{"decision": "grow", "poll": 7}],
            "final": {"rung": 0, "lanes": 3}}
    assert ca.lint_autoscale(good, "A") == []
    errs = ca.lint_autoscale({"records": 1}, "A")
    assert any("decisions" in e for e in errs) \
        and any("final" in e for e in errs)
    bad = dict(good, decisions={"grow": -1})
    assert any("non-negative" in e for e in ca.lint_autoscale(bad, "A"))
    bad = dict(good, transitions=[{"poll": 7}])
    assert any("missing decision" in e
               for e in ca.lint_autoscale(bad, "A"))
    bad = dict(good, final={"rung": 0})
    assert any("final" in e and "lanes" in e
               for e in ca.lint_autoscale(bad, "A"))


def test_lint_chaos_trajectory_block():
    """The chaos recovery trajectory: monotone poll axis, equal-length
    series, and a ladder that moves AT MOST one rung per sample — a
    ladder that jumps rungs is not a ladder."""
    good = {"poll": [1, 2, 3, 4], "rung": [0, 1, 2, 1],
            "lanes": [2, 2, 3, 3], "burn_max": [0.0, 5.0, 9.0, 2.0]}
    assert ca.lint_chaos_trajectory(good, "C") == []
    bad = dict(good, poll=[1, 3, 2, 4])
    assert any("monotone" in e
               for e in ca.lint_chaos_trajectory(bad, "C"))
    bad = dict(good, lanes=[2, 2, 3])
    assert any("length" in e
               for e in ca.lint_chaos_trajectory(bad, "C"))
    bad = dict(good, rung=[0, 2, 2, 1])
    assert any("more than one rung" in e
               for e in ca.lint_chaos_trajectory(bad, "C"))
    bad = dict(good, rung=[0, 1, 0, -1])
    assert any("negative rung" in e
               for e in ca.lint_chaos_trajectory(bad, "C"))
