"""tools/bench_trend.py: the BENCH trend normalization + regression gate.

A multi-round artifact series must render populated (the round-8
bench-trend input parsed to [] — the normalized `metrics` schema exists
so that never recurs) and regression-free; a synthetic injected
regression must fail; CPU and TPU points must never gate against each
other."""

import json

from tools import bench_trend as bt


def _art(tmp_path, n, metrics):
    p = tmp_path / f"BENCH_r{n:02d}.json"
    with open(p, "w") as fh:
        json.dump({"n": n, "cmd": "x", "rc": 0, "tail": "",
                   "schema_version": 1, "metrics": metrics}, fh)
    return str(p)


def _pt(value, name="m", unit="updates/s", backend="tpu"):
    return {"name": name, "value": value, "unit": unit, "backend": backend}


def test_artifacts_render_populated(tmp_path):
    """The acceptance pin, over synthetic artifacts: a multi-round tpu
    series and a cpu series yield a backend-partitioned trend — never
    [] — with no regression at the default tolerance."""
    head = "lattice_site_updates_per_sec_per_chip_poisson4096_rbsor"
    files = [_art(tmp_path, n, [_pt(v, name=head)])
             for n, v in ((1, 1.5e11), (2, 1.51e11), (3, 1.58e11),
                          (4, 1.59e11), (5, 1.59e11))]
    files.append(_art(tmp_path, 6, [_pt(6.7e7, name=head, backend="cpu")]))
    series = bt.build_series(bt.load_points(files))
    assert series, "BENCH artifacts yielded zero trend points"
    assert (head, "tpu") in series and len(series[(head, "tpu")]) >= 4
    assert (head, "cpu") in series
    assert bt.lint(files) == []
    table = bt.render(series)
    assert "r01" in table and "r06" in table and "[tpu]" in table


def test_synthetic_regression_fails(tmp_path):
    """An injected regression beyond tolerance fails; within tolerance
    passes (the make lint trend gate's contract)."""
    files = [_art(tmp_path, 1, [_pt(100.0)]),
             _art(tmp_path, 2, [_pt(80.0)])]  # -20% on a rate
    errs = bt.lint(files, tolerance=0.10)
    assert len(errs) == 1 and "dropped 20.0%" in errs[0]
    assert bt.lint(files, tolerance=0.25) == []
    # within tolerance
    files = [_art(tmp_path, 1, [_pt(100.0)]), _art(tmp_path, 2, [_pt(95.0)])]
    assert bt.lint(files, tolerance=0.10) == []


def test_gate_vs_best_not_last(tmp_path):
    """The gate compares against the BEST earlier point, not merely the
    previous round — a slow multi-round slide cannot ratchet the
    baseline down."""
    files = [_art(tmp_path, i, [_pt(v)])
             for i, v in ((1, 100.0), (2, 94.0), (3, 89.0))]
    errs = bt.lint(files, tolerance=0.10)
    assert len(errs) == 1 and "100" in errs[0]


def test_backend_partition_never_cross_gates(tmp_path):
    """A CPU trend point after strong TPU rounds is NOT a regression —
    the series are keyed (metric, backend)."""
    files = [_art(tmp_path, 1, [_pt(1e11, backend="tpu")]),
             _art(tmp_path, 2, [_pt(5e7, backend="cpu")])]
    assert bt.lint(files) == []
    series = bt.build_series(bt.load_points(files))
    assert ("m", "tpu") in series and ("m", "cpu") in series


def test_latency_direction(tmp_path):
    """ms/step regresses UPWARD; unknown units render but never gate."""
    files = [_art(tmp_path, 1, [_pt(10.0, unit="ms/step")]),
             _art(tmp_path, 2, [_pt(12.0, unit="ms/step")])]
    errs = bt.lint(files, tolerance=0.10)
    assert len(errs) == 1 and "rose" in errs[0]
    files = [_art(tmp_path, 1, [_pt(10.0, unit="bananas")]),
             _art(tmp_path, 2, [_pt(99.0, unit="bananas")])]
    assert bt.lint(files, tolerance=0.10) == []


def test_cpu_series_gate_at_wider_tolerance(tmp_path):
    """cpu series gate at CPU_TOLERANCE (growth containers are different
    hardware round to round — the r08 container runs the identical r06
    poisson loop 21% slower when idle), while tpu series keep the tight
    default; real breakage beyond CPU_TOLERANCE still fails."""
    files = [_art(tmp_path, 1, [_pt(100.0, backend="cpu")]),
             _art(tmp_path, 2, [_pt(76.0, backend="cpu")])]  # -24%
    assert bt.lint(files, tolerance=0.10) == []
    files = [_art(tmp_path, 1, [_pt(100.0, backend="cpu")]),
             _art(tmp_path, 2, [_pt(60.0, backend="cpu")])]  # -40%
    errs = bt.lint(files, tolerance=0.10)
    assert len(errs) == 1 and "35% tolerance" in errs[0]
    # tpu stays tight: the same -24% fails at 10%
    files = [_art(tmp_path, 1, [_pt(100.0, backend="tpu")]),
             _art(tmp_path, 2, [_pt(76.0, backend="tpu")])]
    assert len(bt.lint(files, tolerance=0.10)) == 1


def test_launch_census_direction(tmp_path):
    """launches_per_step gates DOWNWARD by name (ISSUE 17): the static
    census is deterministic, so ANY rise means a fusion regression — and
    the name pin survives a unit-string drift that would otherwise
    un-gate the series."""
    assert bt.higher_is_better("launches/step", "launches_per_step") is False
    assert bt.higher_is_better("bananas", "launches_per_step") is False
    pt = dict(name="launches_per_step", unit="launches/step", backend="cpu")
    files = [_art(tmp_path, 1, [dict(pt, value=0.5)]),
             _art(tmp_path, 2, [dict(pt, value=2.0)])]
    errs = bt.lint(files, tolerance=0.10)
    assert len(errs) == 1 and "launches_per_step" in errs[0] \
        and "rose" in errs[0]
    assert bt.lint([_art(tmp_path, 1, [dict(pt, value=0.5)]),
                    _art(tmp_path, 2, [dict(pt, value=0.5)])]) == []
    # the small serving-regime line is name-pinned downward too
    assert bt.higher_is_better(
        "bananas", "ns2d_small_ms_per_step") is False


def test_legacy_artifact_fallback(tmp_path):
    """Artifacts without a normalized metrics list fall back to the same
    normalizer over their parsed* blocks (never tail scraping)."""
    p = tmp_path / "BENCH_r01.json"
    with open(p, "w") as fh:
        json.dump({"n": 1, "cmd": "x", "rc": 0, "tail": "",
                   "parsed": {"metric": "legacy", "value": 5.0,
                              "unit": "updates/s", "backend": "pallas"}}, fh)
    pts = bt.load_points([str(p)])
    assert pts == [{"round": 1, "name": "legacy", "value": 5.0,
                    "unit": "updates/s", "backend": "tpu",
                    "file": "BENCH_r01.json"}]


def test_empty_input_is_a_violation(tmp_path):
    """The trend pass FAILS on an empty series — the round-8 `[]` shape
    is a lint error, not a silent pass."""
    assert bt.lint([]) != []
    p = tmp_path / "BENCH_r01.json"
    with open(p, "w") as fh:
        json.dump({"n": 1, "cmd": "x", "rc": 0, "tail": ""}, fh)
    assert any("zero trend points" in e for e in bt.lint([str(p)]))


def test_comm_hidden_fraction_higher_is_better(tmp_path):
    """The overlap headline gates UPWARD: a drop in comm_hidden_fraction
    means exchange time slid back onto the critical path (ROADMAP item 2;
    NAME_DIRECTIONS overrides the unit heuristic for this metric)."""
    assert bt.higher_is_better("fraction", "comm_hidden_fraction") is True
    assert bt.higher_is_better("fraction") is None  # unit alone: no gate
    pt = dict(name="comm_hidden_fraction", unit="fraction", backend="tpu")
    files = [_art(tmp_path, 1, [dict(pt, value=0.6)]),
             _art(tmp_path, 2, [dict(pt, value=0.3)])]
    errs = bt.lint(files, tolerance=0.10)
    assert len(errs) == 1 and "comm_hidden_fraction" in errs[0] \
        and "dropped" in errs[0]
    files = [_art(tmp_path, 1, [dict(pt, value=0.6)]),
             _art(tmp_path, 2, [dict(pt, value=0.58)])]
    assert bt.lint(files, tolerance=0.10) == []


def test_comm_hidden_fraction_normalized_from_block(tmp_path):
    """collect_metrics surfaces the merged comm_hidden_fraction block as
    a normalized metric, backend-tagged from the run it came from (a CPU
    smoke plane must not seed a chip-gating series)."""
    from tools._artifact import collect_metrics

    rec = {"comm_hidden_fraction": {"mode": "trace", "hidden_fraction": 0.4},
           "telemetry_summary": {"backend": "cpu"}}
    (m,) = collect_metrics(rec)
    assert m == {"name": "comm_hidden_fraction", "value": 0.4,
                 "unit": "fraction", "backend": "cpu"}
    rec["telemetry_summary"]["backend"] = "tpu"
    assert collect_metrics(rec)[0]["backend"] == "tpu"
    # a null hidden fraction (attribution failure) yields no point
    rec["comm_hidden_fraction"]["hidden_fraction"] = None
    assert collect_metrics(rec) == []


def test_autoscale_directions(tmp_path):
    """The control-plane health lines gate DOWNWARD by name (ISSUE 19):
    a longer time-to-recover or more capacity flaps under the same
    chaos script is a policy regression, whatever the unit says."""
    assert bt.higher_is_better(
        "ms", "autoscale_time_to_recover_ms") is False
    assert bt.higher_is_better("bananas", "autoscale_flaps") is False
    pt = dict(name="autoscale_time_to_recover_ms", unit="ms",
              backend="cpu")
    files = [_art(tmp_path, 1, [dict(pt, value=4000.0)]),
             _art(tmp_path, 2, [dict(pt, value=9000.0)])]
    errs = bt.lint(files, tolerance=0.35)
    assert len(errs) == 1 and "autoscale_time_to_recover_ms" in errs[0]
    assert bt.lint([_art(tmp_path, 1, [dict(pt, value=4000.0)]),
                    _art(tmp_path, 2, [dict(pt, value=4100.0)])],
                   tolerance=0.35) == []


def test_autoscale_normalized_from_block(tmp_path):
    """collect_metrics surfaces the merged autoscale block's flap count
    and recovery latency as normalized, backend-tagged trend points."""
    from tools._artifact import collect_metrics

    rec = {"autoscale": {"records": 25, "flaps": 0,
                         "time_to_recover_ms": 4204.7},
           "telemetry_summary": {"backend": "cpu"}}
    pts = {m["name"]: m for m in collect_metrics(rec)}
    assert pts["autoscale_flaps"]["value"] == 0
    assert pts["autoscale_flaps"]["backend"] == "cpu"
    assert pts["autoscale_time_to_recover_ms"]["value"] == 4204.7
    assert pts["autoscale_time_to_recover_ms"]["unit"] == "ms"
    # an unfinished storm (no recovery) yields no latency point
    rec["autoscale"]["time_to_recover_ms"] = None
    names = [m["name"] for m in collect_metrics(rec)]
    assert "autoscale_time_to_recover_ms" not in names \
        and "autoscale_flaps" in names
