"""Cells, metrics and layers are found by name, and the run refuses to
time anything but a TPU."""

import json
import os
import shutil
import subprocess
import sys

import pytest

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH)
sys.path.insert(0, BENCH)

import spec  # noqa: E402
import tracered  # noqa: E402


@pytest.fixture()
def copy(tmp_path):
    """A copy of the benchmark directory and BENCHMARK.json."""
    here = tmp_path / "benchmark"
    shutil.copytree(BENCH, here, ignore=shutil.ignore_patterns(
        "__pycache__", "tests"))
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), tmp_path)
    return tmp_path


def test_every_cell_loads():
    bench = spec.benchmark()
    for w in bench["workloads"]:
        cell = spec.cell(w["name"], bench)
        assert cell["par"]["imax"] > 0
        assert set(cell["limits"]["limits"]) >= {"u_gap", "v_gap", "p_gap",
                                                 "steps_gap"}
    for m in bench["per_layer"]:
        assert callable(spec.reader(m["name"]))


def test_added_files_are_found_without_edits(copy):
    """A new configuration with its reference, traffic mix, cell limits,
    metric and layer, each dropped in as a file of its own; only
    BENCHMARK.json gains entries, and no existing file changes."""
    here = copy / "benchmark"
    before = {p: p.read_bytes() for p in here.rglob("*") if p.is_file()}
    cfg = json.loads((here / "configs" / "dcavity2d.json").read_text())
    cfg["par"]["re"] = 100.0
    (here / "configs" / "cavity_re100.json").write_text(json.dumps(cfg))
    (here / "references" / "cavity_re100.py").write_bytes(
        (here / "references" / "dcavity2d.py").read_bytes())
    traffic = json.loads((here / "traffic" / "n256-converged.json")
                         .read_text())
    traffic["par"]["imax"] = traffic["par"]["jmax"] = 512
    (here / "traffic" / "n512.json").write_text(json.dumps(traffic))
    (here / "limits" / "cavity_re100.n512.json").write_text(json.dumps(
        {"limits": {"u_gap": 1, "v_gap": 1, "p_gap": 1, "steps_gap": 0}}))
    (here / "metrics" / "busy_ms.py").write_text(
        "def read(ctx):\n    b = ctx.busy_s()\n"
        "    return None if b is None else 1e3 * b\n")
    (here / "layers" / "zz_copies.json").write_text(json.dumps(
        {"layer": "copies", "patterns": ["^copy"]}))
    bench = json.loads((copy / "BENCHMARK.json").read_text())
    bench["configs"].append({"name": "cavity_re100", "source": "x",
                             "file": "benchmark/configs/cavity_re100.json",
                             "reduced": [], "why": "x"})
    bench["workloads"].append({"name": "cavity_re100.n512",
                               "config": "cavity_re100", "traffic": "n512",
                               "chips": 1, "why": "x"})
    bench["per_layer"].append({"name": "busy_ms", "unit": "ms",
                               "better": "lower", "source": "device_trace",
                               "layer": "device", "moves": "ms_per_step"})
    cell = spec.cell("cavity_re100.n512", bench, here=str(here))
    assert cell["par"]["re"] == 100.0 and cell["par"]["imax"] == 512
    assert cell["reference"].field_names(cell["par"]) == ("u", "v", "p")
    names = [m["name"] for m in spec.metrics_for(bench, "cavity_re100.n512",
                                                 "per_layer")]
    assert "busy_ms" in names and "exchange_exposed_ms_per_step" not in names
    read = spec.reader("busy_ms", here=str(here))
    rules = spec.layer_rules(here=str(here))
    assert [r["key"] for r in rules][-1] == "zz_copies"
    trace = {"devices": [{"name": "/device:TPU:0", "modules": [],
                          "ops": [["copy.1", 0, 10, "copy.1"]]}],
             "host": [["bench.replay", 0, 20]]}
    red = tracered.reduce(trace, rules)
    assert red["devices"][0]["layer_ns"] == {"zz_copies": 10}
    ctx = tracered.Context(red, 1, cell["par"], {}, 0.0, 1)
    assert read(ctx) == pytest.approx(10e-6)
    after = {p: p.read_bytes() for p in before}
    assert after == before


def test_run_fails_off_tpu():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    proc = subprocess.run(
        [sys.executable, os.path.join(BENCH, "run.py"), "--workload",
         "dcavity2d.n256-converged", "--seed", "3000000000", "--seconds",
         "1", "--trace", "0"], cwd=ROOT, env=env, capture_output=True,
        text=True, timeout=300)
    assert proc.returncode != 0
    assert "tpu" in proc.stderr.lower()
    assert not proc.stdout.strip().endswith("}")


def test_run_fails_without_the_program(tmp_path):
    shutil.copytree(BENCH, tmp_path / "benchmark",
                    ignore=shutil.ignore_patterns("__pycache__"))
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), tmp_path)
    proc = subprocess.run(
        [sys.executable, "benchmark/run.py", "--workload",
         "dcavity2d.n256-converged", "--seed", "1", "--seconds", "1",
         "--trace", "0"], cwd=tmp_path, capture_output=True, text=True,
        timeout=300)
    assert proc.returncode != 0
    assert not proc.stdout.strip()
