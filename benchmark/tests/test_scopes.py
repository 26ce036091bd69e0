"""The scope reduction (benchmark/scopes.py) on traces whose answers are
known by hand, on the committed chip fixtures, and the dump script's
glue at a small size on the CPU."""

import gzip
import json
import os
import sys

import pytest

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, BENCH)

import scopes  # noqa: E402
import spec  # noqa: E402
import tracered  # noqa: E402

FIXTURES = os.path.join(BENCH, "tests", "fixtures")

HLO = """HloModule jit_chunk_fn, entry_computation_layout={()->()}

ENTRY %main {
  %ns2d_pre.1 = f32[8] custom-call(), custom_call_target="tpu_custom_call", metadata={op_name="jit(chunk_fn)/while/body/phases/pallas_call" source_file="x.py"}
  %copy.2 = f32[4,8] copy(f32[4,8] %a), metadata={op_name="jit(chunk_fn)/while/body/solve/jit(pack)/transpose"}
  %sor2d_rb_quarters.3 = f32[4,8] custom-call(), custom_call_target="tpu_custom_call", metadata={op_name="jit(chunk_fn)/while/body/solve/while/body/pallas_call"}
  ROOT %fusion.4 = f32[] fusion(f32[8] %b), kind=kLoop, calls=%f, metadata={op_name="jit(chunk_fn)/while/body/phases/reduce_max"}
  %add.5 = s32[] add(s32[] %k, s32[] %one), metadata={op_name="jit(chunk_fn)/while/body/add"}
}
"""


def _op(name, start, dur, tail="f32[8] copy()"):
    return [name, start, dur, f"%{name} = {tail}"]


KERNEL = 'f32[8] custom-call(), custom_call_target="tpu_custom_call"'


def synthetic():
    """One device, times in ns. Replay [0, 200): the chunk module runs
    [0, 90) and [100, 190); the host dispatches [0, 5) and [95, 100),
    waits [5, 90) and [100, 195), syncs [90, 95) and [195, 200).

    first execution: pre [0,10), copy [10,20), sor [20,50), sor [50,60),
    fusion [60,70), an unscoped add [70,72). Another module's op [96,98).
    second execution: pre [100,110), sor [110,150). Device idle: [72,90)
    and [150,195) in drive.wait (the device's own); [90,96), [98,100) and
    [195,200) while the host syncs or dispatches (host-bound: 13 ns). A
    reset op [250, 260) outside the replay counts nowhere.
    """
    ops = [
        _op("ns2d_pre.1", 0, 10, KERNEL),
        _op("copy.2", 10, 10),
        _op("sor2d_rb_quarters.3", 20, 30, KERNEL),
        _op("sor2d_rb_quarters.3", 50, 10, KERNEL),
        _op("fusion.4", 60, 10),
        _op("add.5", 70, 2),
        _op("copy.9", 96, 2),
        _op("ns2d_pre.1", 100, 10, KERNEL),
        _op("sor2d_rb_quarters.3", 110, 40, KERNEL),
        _op("copy.2", 250, 10),
    ]
    modules = [["jit_chunk_fn(1)", 0, 90], ["jit_other(2)", 95, 4],
               ["jit_chunk_fn(1)", 100, 90], ["jit_chunk_fn(1)", 250, 20]]
    host = [["bench.replay", 0, 200], ["bench.reset", 240, 30],
            ["drive.dispatch", 0, 5], ["drive.wait", 5, 85],
            ["drive.sync", 90, 5], ["drive.dispatch", 95, 5],
            ["drive.wait", 100, 95], ["drive.sync", 195, 5],
            ["$_array.py:631 _value", 80, 8]]
    return {"devices": [{"name": "/device:TPU:0", "ops": ops,
                         "modules": modules}], "host": host}


def test_op_scopes_from_hlo_text():
    table = scopes.op_scopes(HLO)
    assert table["module"] == "jit_chunk_fn"
    assert table["ops"] == {"ns2d_pre.1": "phases", "copy.2": "solve",
                            "sor2d_rb_quarters.3": "solve",
                            "fusion.4": "phases"}
    assert table["names"] == sorted(["ns2d_pre.1", "copy.2", "add.5",
                                     "sor2d_rb_quarters.3", "fusion.4"])


def test_reduce_synthetic():
    red = scopes.reduce(synthetic(), scopes.op_scopes(HLO))
    assert red["window_ns"] == 200 and red["drive_spans"]
    (dev,) = red["devices"]
    assert dev["scope_ns"] == {"solve": 10 + 30 + 10 + 40,
                               "phases": 10 + 10 + 10}
    assert dev["solve_launches"] == 3
    # idle inside the replay: [72,96) [98,100) [150,200); drive.wait covers
    # [72,90) and [150,195)
    assert dev["host_bound_idle_ns"] == 6 + 2 + 5


def test_readers_synthetic():
    m = scopes.metrics(synthetic(), scopes.op_scopes(HLO), steps=2)
    assert m["solve_scope_ms_per_step"] == pytest.approx(90e-6 / 2)
    assert m["phases_scope_ms_per_step"] == pytest.approx(30e-6 / 2)
    assert m["solve_launches_per_step"] == 1.5
    assert m["host_bound_idle_share"] == pytest.approx(100 * 13 / 200)


def test_idle_gaps_named_by_program_span():
    """The gap [72, 96) has a Python frame inside drive.wait over its
    midpoint: today's rule names the frame, the program span wins here."""
    trace = synthetic()
    new = scopes.idle_gaps(trace)
    old = tracered.reduce(trace, [])["idle_gaps"]
    assert [n for n, _ in new] == ["drive.wait", "drive.dispatch"]
    assert [n for n, _ in old] == ["$_array.py:631 _value", "drive.dispatch"]
    assert [g for _, g in new] == [g for _, g in old] == pytest.approx(
        [24e-9, 2e-9])


def test_nothing_to_read_without_program_scopes():
    """A program without scopes or drive spans (the first recorded chip trace)
    reads None for all four numbers, and its idle gaps keep today's
    names."""
    with gzip.open(os.path.join(FIXTURES, "v5e_n4096_trace.json.gz"),
                   "rt") as fh:
        trace = json.load(fh)
    steps = trace["meta"]["steps"]
    m = scopes.metrics(trace, {"module": "", "ops": {}, "names": []}, steps)
    assert m == dict.fromkeys(m)
    red = tracered.reduce(trace, spec.layer_rules())
    assert scopes.idle_gaps(trace) == red["idle_gaps"]


def test_dump_scopes_glue_on_cpu(tmp_path, monkeypatch):
    """dump_scopes at a small size on the CPU: it builds the cell, reads
    the chunk's compiled HLO, traces its replays and writes its files.
    The CPU trace has no TPU plane, so the numbers read None here."""
    import dump_scopes
    import run

    run.environment()
    real = spec.cell

    def small(name):
        cell = real(name)
        cell["par"].update(imax=16, jmax=16, te=0.01, tpu_sor_inner=1)
        return cell

    monkeypatch.setattr(spec, "cell", small)
    import jax

    monkeypatch.setattr(run, "devices_for", lambda chips: jax.devices())
    rc = dump_scopes.main(["--workload", "dcavity2d.n256-converged",
                           "--seed", "2147483659", "--replays", "1",
                           "--out", str(tmp_path)])
    assert rc == 0
    table = json.load(open(tmp_path / "scopes.json"))
    assert set(table["ops"].values()) == {"solve", "phases"}
    res = json.load(open(tmp_path / "result.json"))
    assert res["steps"] > 0 and res["chunks"] >= 1
    assert res["region_us"]["profiler_on"] > 0
    assert res["coverage"] is None  # no TPU plane in a CPU trace


def _scoped_fixture():
    with gzip.open(os.path.join(FIXTURES, "v5e_n4096_scoped_trace.json.gz"),
                   "rt") as fh:
        return json.load(fh)


def test_recorded_chip_trace_with_scopes():
    """One chunk of the 4096² cell on the chip (benchmark/dump_scopes.py,
    this program): the four numbers as read there, and the kernels by
    name."""
    trace = _scoped_fixture()
    m = scopes.metrics(trace, trace["scopes"], trace["meta"]["steps"])
    assert m == pytest.approx({
        "solve_scope_ms_per_step": 11.58606134375,
        "phases_scope_ms_per_step": 1.76191725,
        "solve_launches_per_step": 7.0,
        "host_bound_idle_share": 0.4534193216444106,
    }, rel=1e-9)
    kernels = {op[0].split(".")[0] for op in trace["devices"][0]["ops"]
               if scopes.KERNEL_RE.search(op[3])}
    assert kernels == {"ns2d_pre", "ns2d_post", "sor2d_rb_quarters"}
    assert {n for n, _g in scopes.idle_gaps(trace)} == {"drive.wait"}


def test_committed_rules_classify_named_kernels_as_before():
    """Kernel names change the HLO instruction names only: the committed
    layer rules still put the SOR kernel in solve and PRE/POST in
    phases."""
    rules = spec.layer_rules()
    want = {"sor2d_rb_quarters": "solve", "ns2d_pre": "phases",
            "ns2d_post": "phases"}
    for n, _s, _d, lab in _scoped_fixture()["devices"][0]["ops"]:
        base = n.split(".")[0]
        if base in want:
            assert tracered.classify(lab, rules) == want[base], lab[:120]
