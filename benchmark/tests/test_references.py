"""Each configuration brings its own reference module, found by the
configuration's name (benchmark/references/<config>.py), and the harness
reaches the reference only through it."""

import json
import os
import sys
import types

import numpy as np
import pytest

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, BENCH)

import spec  # noqa: E402
from test_faults import MESH_2X2, SMALL  # noqa: E402
from test_loading import copy  # noqa: E402,F401  (the fixture)

REQUIRED = ("check_covered", "field_names", "initial_fields", "run_segment")
SMALL_ALL = dict(SMALL, **{"dcavity2d.n4096-capped-2x2": MESH_2X2})

# a deployment the repo does not have, added as files alone: a decaying
# field, with a solid block the comparison leaves out
TOY_REFERENCE = '''
import numpy as np
import jax.numpy as jnp


def check_covered(par):
    if par["name"] != "toy":
        raise ValueError("toy only")


def field_names(par):
    return ("u", "p")


def initial_fields(par, traffic, seed):
    rng = np.random.default_rng(seed)
    shape = (par["jmax"] + 2, par["imax"] + 2)
    return tuple(jnp.asarray(rng.standard_normal(shape), jnp.float32)
                 for _ in range(2))


def run_segment(par, fields, dtype):
    steps = int(round(par["te"] / par["dt"]))
    out = tuple(a.astype(dtype) for a in fields)
    for _ in range(steps):
        out = tuple(a * (1 - par["dt"]) for a in out)
    return tuple(a.astype(jnp.float32) for a in out), steps


def compared(par):
    mask = np.ones((par["jmax"], par["imax"]), bool)
    mask[2:4, 2:4] = False
    return mask
'''


def add_toy(copy, reference: bool = True) -> dict:
    """The toy configuration's files and entries; returns the benchmark."""
    here = copy / "benchmark"
    (here / "configs" / "toy.json").write_text(json.dumps(
        {"par": {"name": "toy", "dt": 0.1}}))
    if reference:
        (here / "references" / "toy.py").write_text(TOY_REFERENCE)
    (here / "traffic" / "n8.json").write_text(json.dumps(
        {"par": {"imax": 8, "jmax": 8, "te": 0.3}}))
    (here / "limits" / "toy.n8.json").write_text(json.dumps(
        {"limits": {"u_gap": 0, "p_gap": 0, "steps_gap": 0}}))
    bench = json.loads((copy / "BENCHMARK.json").read_text())
    bench["configs"].append({"name": "toy", "source": "x",
                             "file": "benchmark/configs/toy.json",
                             "reduced": [], "why": "x"})
    bench["workloads"].append({"name": "toy.n8", "config": "toy",
                               "traffic": "n8", "chips": 1, "why": "x"})
    return bench


@pytest.mark.parametrize("config", [c["name"] for c in
                                    spec.benchmark()["configs"]])
def test_every_configuration_has_a_reference(config):
    bench = spec.benchmark()
    names = [w["name"] for w in bench["workloads"] if w["config"] == config]
    assert names, f"no cell uses {config}"
    for name in names:
        mod = spec.cell(name, bench)["reference"]
        assert mod.__file__ == os.path.join(BENCH, "references",
                                            config + ".py")
        for fn in REQUIRED:
            assert callable(getattr(mod, fn, None)), (config, fn)


@pytest.mark.parametrize("name", sorted(SMALL_ALL))
def test_dispatch_matches_the_direct_chain(name):
    """The reference reached through the cell's module gives, bit for bit,
    the fields the seeded initial state and the plain segment give when
    called directly."""
    import run

    run.environment()
    import harness
    import jax.numpy as jnp
    import reference
    import seedfields

    cell = spec.cell(name)
    cell["par"].update(SMALL_ALL[name])
    par, pert = cell["par"], cell["traffic_file"]["perturbation"]
    seed = 3_000_000_019
    direct_init = seedfields.make_fields(
        par, seedfields.coefficients(seed, pert["modes"]), pert["amplitude"])
    init = cell["reference"].initial_fields(par, cell["traffic_file"], seed)
    assert len(init) == len(cell["reference"].field_names(par))
    for a, b in zip(init, direct_init):
        assert np.array_equal(np.asarray(a), np.asarray(b))
    direct, direct_steps = reference.run_segment(par, direct_init,
                                                 jnp.float32)
    out, steps = harness.reference_fields(cell, seed, "float32")
    assert steps == direct_steps > 0
    for a, b in zip(out, direct):
        assert np.array_equal(a, np.asarray(b))


def test_configuration_added_as_files_alone(copy):
    import harness

    here = copy / "benchmark"
    before = {p: p.read_bytes() for p in here.rglob("*") if p.is_file()}
    bench = add_toy(copy)
    cell = spec.cell("toy.n8", bench, here=str(here))
    cell["reference"].check_covered(cell["par"])
    ref, steps = harness.reference_fields(cell, 4, "float32")
    assert steps == 3 and [a.shape for a in ref] == [(10, 10)] * 2
    prog = dict(zip(("u", "p"), ref))
    checks = harness.judge(harness.readings(cell, prog, steps, ref, steps),
                           cell["limits"]["limits"])
    assert harness.passed(checks), checks
    assert {p: p.read_bytes() for p in before} == before


def test_compared_is_honoured():
    """A change inside the cells `compared` leaves out reads 0, the same
    change outside reads above 0, and without `compared` both count."""
    import harness

    cell = spec.cell("dcavity2d.n256-converged")
    cell["par"].update(imax=16, jmax=16)
    plain = cell["reference"]
    mask = np.ones((16, 16), bool)
    mask[4:8, 4:8] = False
    masked = types.SimpleNamespace(field_names=plain.field_names,
                                   compared=lambda par: mask)
    rng = np.random.default_rng(0)
    ref = tuple(rng.standard_normal((18, 18)).astype(np.float32)
                for _ in range(3))
    names = plain.field_names(cell["par"])

    def gaps(module, at):
        prog = {f: a.copy() for f, a in zip(names, ref)}
        for f in ("u", "p"):
            prog[f][at] += 1.0
        rd = harness.readings(dict(cell, reference=module), prog, 5, ref, 5)
        return rd["u_gap"], rd["v_gap"], rd["p_gap"]

    inside, outside = (1 + 5, 1 + 6), (1 + 12, 1 + 2)   # ghost ring first
    assert gaps(masked, inside) == (0.0, 0.0, 0.0)
    u, v, p = gaps(masked, outside)
    assert u > 0 and p > 0 and v == 0
    u, v, p = gaps(plain, inside)
    assert u > 0 and p > 0 and v == 0


def test_configuration_without_a_reference_is_refused(copy):
    bench = add_toy(copy, reference=False)
    with pytest.raises(FileNotFoundError,
                       match=r"references[\\/]toy\.py"):
        spec.cell("toy.n8", bench, here=str(copy / "benchmark"))


@pytest.mark.parametrize("update,refused", [
    ({}, False),
    ({"tpu_solver": "mg"}, True),
    ({"name": "canal"}, True),
    ({"tau": 0.0}, True),
])
def test_refusal_stops_setup_before_the_cli(monkeypatch, update, refused):
    import run

    run.environment()
    import harness
    from pampi_tpu import cli

    class Reached(Exception):
        pass

    def no_cli(*_a, **_kw):
        raise Reached

    monkeypatch.setattr(cli, "run", no_cli)
    cell = spec.cell("dcavity2d.n256-converged")
    cell["par"].update(update)
    with pytest.raises(ValueError if refused else Reached):
        harness.setup(cell, 1)
