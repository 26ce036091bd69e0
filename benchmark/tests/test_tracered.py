"""The trace reduction on small traces whose answers are known by hand."""

import gzip
import json
import os
import sys

import pytest

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, BENCH)

import spec  # noqa: E402
import tracered  # noqa: E402

FIXTURES = os.path.join(BENCH, "tests", "fixtures")

RULES = [
    {"key": "exchange", "patterns": ["collective-permute"]},
    {"key": "phases", "patterns": ["^%fused_(pre|post)"]},
    {"key": "solve", "patterns": ["^%sor"]},
]


def synthetic():
    """Two replay spans; one device; times in ns.

    replay A [0, 100): module 1 holds ops [10,30) pre, [30,60) sor,
    [50,70) collective-permute (10 of it under sor), [70,80) post;
    module 2 holds [90,95) sor. Replay B [200, 250): [210,240) sor.
    The reset between them [100,200) has an op [150,160) the window must
    not count, and a while container spans everything.
    """
    ops = [
        ["fused_pre.1", 10, 20, "%fused_pre.1 = f32[8] custom-call()"],
        ["sor.2", 30, 30, "%sor.2 = f32[8] custom-call()"],
        ["collective-permute.3", 50, 20,
         "%collective-permute.3 = f32[8] collective-permute(f32[8] %x)"],
        ["fused_post.4", 70, 10, "%fused_post.4 = f32[8] custom-call()"],
        ["sor.2", 90, 5, "%sor.2 = f32[8] custom-call()"],
        ["copy.9", 150, 10, "%copy.9 = f32[8] copy(f32[8] %x)"],
        ["sor.2", 210, 30, "%sor.2 = f32[8] custom-call()"],
        ["while.7", 0, 250, "%while.7 = (f32[8]) while((f32[8]) %t)"],
    ]
    modules = [["jit_chunk", 5, 80], ["jit_chunk", 88, 10],
               ["jit_chunk", 205, 40]]
    host = [["bench.replay", 0, 100], ["bench.reset", 100, 100],
            ["bench.replay", 200, 50], ["PjitFunction(chunk)", 80, 12]]
    return {"devices": [{"name": "/device:TPU:0", "ops": ops,
                         "modules": modules}], "host": host}


def test_merge_and_exposed():
    m = tracered.merge([[5, 7], [0, 2], [1, 3], [7, 9]])
    assert m == [[0, 3], [5, 9]]
    assert tracered.length(m) == 7
    assert tracered.exposed([[0, 10]], [[2, 4], [6, 8]]) == 6
    assert tracered.exposed([[0, 10]], []) == 10
    assert tracered.exposed([[0, 10]], [[-5, 20]]) == 0


def test_reduce_synthetic():
    red = tracered.reduce(synthetic(), RULES)
    assert red["window_ns"] == 150
    (dev,) = red["devices"]
    # busy: [10,80) + [90,95) + [210,240); the reset's copy and the while
    # container do not count
    assert dev["busy_ns"] == 70 + 5 + 30
    assert dev["layer_ns"]["solve"] == 30 + 5 + 30
    assert dev["layer_ns"]["phases"] == 30
    assert dev["layer_ns"]["exchange"] == 20
    # [50,70) exchange: [50,60) under sor, [60,70) bare
    assert dev["exposed_ns"]["exchange"] == 10
    # module 1's ops end at 80, module 2's begin at 90; replay B has one
    assert dev["chunk_gaps_ns"] == [10]
    ctx = tracered.Context(red, steps=3, par={}, peak={}, compile_s=1.0,
                           chips=1)
    assert ctx.busy_s() == pytest.approx(105e-9)
    assert ctx.window_s() == pytest.approx(150e-9)
    assert spec.reader("device_idle_share")(ctx) == pytest.approx(30.0)
    assert spec.reader("solve_ms_per_step")(ctx) == pytest.approx(65e-6 / 3)
    assert spec.reader("exchange_exposed_ms_per_step")(ctx) == \
        pytest.approx(10e-6 / 3)
    assert spec.reader("chunk_gap_ms")(ctx) == pytest.approx(10e-6)
    # the longest idle gap lies in the dispatch span [80, 92)
    assert red["idle_gaps"][0][0] == "PjitFunction(chunk)"
    assert red["idle_gaps"][0][1] == pytest.approx(10e-9)
    assert red["top_ops"][0][0] == "sor.2 (solve)"


def test_reader_returns_nothing_without_ops():
    trace = synthetic()
    trace["devices"][0]["ops"] = [o for o in trace["devices"][0]["ops"]
                                  if not o[0].startswith("collective")]
    red = tracered.reduce(trace, RULES)
    ctx = tracered.Context(red, steps=3, par={}, peak={}, compile_s=1.0,
                           chips=1)
    assert spec.reader("exchange_exposed_ms_per_step")(ctx) is None
    assert tracered.Context(None, 3, {}, {}, 1.0, 1).busy_s() is None


@pytest.mark.parametrize("label,layer", [
    # the 2x2 pack's old flat gather by a same-length index vector: the
    # pack now slices rows, then lanes, so the solve rule no longer claims it
    ("%fusion.130 = f32[1050625]{0:T(1024)} fusion(f32[2050,2050]"
     "{1,0:T(8,128)} %conditional.4, s32[1050625]{0:T(1024)S(1)} "
     "%custom-call.32), kind=kCustom", "other"),
    # the row scatter that unpacks it is not claimed
    ("%fusion.150 = f32[2050,2050]{1,0:T(8,128)S(1)} fusion(s32[1025]"
     "{0:T(1024)} %fusion.125, f32[1025,2050]{1,0:T(8,128)S(1)} %copy.237, "
     "f32[]{:T(128)} %constant.1944..sunk), kind=kCustom", "other"),
    # a gather whose index vector is not the output's length
    ("%fusion.9 = f32[2049]{0} fusion(f32[2050,2050]{1,0} %p, s32[4098]{0} "
     "%i), kind=kLoop", "other"),
    ("%tpu_custom_call.3 = f32[4,2208,2176]{2,1,0} custom-call(f32[4,2208,"
     "2176]{2,1,0} %x), custom_call_target=\"tpu_custom_call\"", "solve"),
    ("%collective-permute-done.15 = f32[2,2054]{1,0} collective-permute-done("
     "(f32[2,2054]{1,0}, f32[2,2054]{1,0}) %s)", "exchange"),
])
def test_layer_rules_on_recorded_labels(label, layer):
    assert tracered.classify(label, spec.layer_rules()) == layer


def test_recorded_chip_trace():
    """A trace recorded on a TPU v5e (dump_trace.py on
    dcavity2d.n4096-capped, cut to its first 32-step chunk): the layer
    rules find the solve and the phase kernels, and every share lies in
    (0, 100]."""
    path = os.path.join(FIXTURES, "v5e_n4096_trace.json.gz")
    with gzip.open(path, "rt") as fh:
        trace = json.load(fh)
    meta = trace.pop("meta")
    red = tracered.reduce(trace, spec.layer_rules())
    (dev,) = red["devices"]
    assert dev["layer_ns"].get("solve", 0) > 0
    assert dev["layer_ns"].get("phases", 0) > 0
    assert 0 < dev["busy_ns"] <= red["window_ns"]
    ctx = tracered.Context(red, meta["steps"], meta["par"],
                           spec.peaks("TPU v5 lite"), 1.0, 1)
    for name in ("solve_roofline", "phases_roofline", "device_idle_share"):
        val = spec.reader(name)(ctx)
        assert val is not None and 0 < val <= 100, (name, val)
