"""Program spans (utils/profiling.py): the `solve` and `phases` named
scopes every NS family stamps into its chunk's op_name metadata, the
three host spans of the drive loop, and `profiling.region` as the one
span primitive (the trace annotation always, the wall table only under
PAMPI_PROFILE). A profiler trace reads these names on the device's clock;
the tests here pin what the program puts into it."""

import re

import jax
import jax.numpy as jnp
import pytest
from jax import core

from pampi_tpu.models import _driver
from pampi_tpu.models._driver import drive_chunks
from pampi_tpu.utils import profiling as prof
from pampi_tpu.utils.params import Parameter

SCOPES = ("solve", "phases")


def _paths(jaxpr, prefix=""):
    """(scope path, primitive, kernel name) of every equation, nested
    jaxprs included; the path joins the name stacks from the top."""
    out = []
    for eqn in jaxpr.eqns:
        path = f"{prefix}/{eqn.source_info.name_stack}"
        out.append((path, eqn.primitive.name, eqn.params.get("name")))
        for sub in core.jaxprs_in_params(eqn.params):
            out += _paths(sub, path)
    return out


def _under(path: str, scope: str) -> bool:
    return scope in path.split("/")


def _solver(family: str, fuse: str):
    base = dict(re=10.0, te=0.01, tau=0.5, itermax=12, eps=1e-4, omg=1.7,
                tpu_fuse_phases=fuse, tpu_dtype="float32")
    if family == "ns2d":
        from pampi_tpu.models.ns2d import NS2DSolver

        s = NS2DSolver(Parameter(name="dcavity", imax=16, jmax=16, **base))
        return s._chunk_fn, s.initial_state()
    if family == "ns3d":
        from pampi_tpu.models.ns3d import NS3DSolver

        s = NS3DSolver(Parameter(name="dcavity3d", imax=8, jmax=8, kmax=8,
                                 **base))
        return s._chunk_fn, s.initial_state()
    from pampi_tpu.parallel.comm import CartComm

    if family == "ns3d_dist":
        from pampi_tpu.models.ns3d_dist import NS3DDistSolver

        s = NS3DDistSolver(Parameter(name="dcavity3d", imax=8, jmax=8, kmax=8,
                                     **base), CartComm(ndims=3, dims=(2, 2, 1)))
        return s._chunk_sm, s.initial_state()
    from pampi_tpu.models.ns2d_dist import NS2DDistSolver

    s = NS2DDistSolver(Parameter(name="dcavity", imax=16, jmax=16, **base),
                       CartComm(ndims=2, dims=(2, 2)))
    return s._chunk_sm, s.initial_state()


@pytest.mark.parametrize("family,fuse", [
    ("ns2d", "off"), ("ns2d", "on"), ("ns3d", "off"), ("ns3d", "on"),
    ("ns2d_dist", "off"), ("ns2d_dist", "on"), ("ns3d_dist", "off"),
])
def test_chunk_carries_solve_and_phases_scopes(family, fuse):
    """The lowered chunk carries both scopes in its op locations, and every
    solve op sits under `solve`: the SOR kernels and the solve's own
    convergence loop (every while loop inside the chunk loop), never under
    `phases`; the fused PRE/POST kernels sit under `phases`."""
    chunk, state = _solver(family, fuse)
    text = chunk.lower(*state).as_text(debug_info=True)
    for scope in SCOPES:
        assert re.search(rf'loc\("[^"]*/{scope}/', text), scope
    paths = _paths(jax.make_jaxpr(chunk)(*state).jaxpr)
    assert not [p for p, _, _ in paths
                if _under(p, "solve") and _under(p, "phases")]
    loops = [p for p, prim, _ in paths if prim == "while"]
    inner = [p for p in loops if any(q != p and p.startswith(q)
                                     for q in loops)]
    assert inner and all(_under(p, "solve") for p in inner), loops
    kernels = [(p, n) for p, prim, n in paths if prim == "pallas_call"]
    for p, name in kernels:
        assert _under(p, "solve" if name.startswith("sor") else "phases"), \
            (name, p)
    if fuse == "on":
        assert {n for _, n in kernels} >= {f"{family[:4]}_pre",
                                          f"{family[:4]}_post"}


def _recording(monkeypatch):
    seen = []
    real = prof.region

    def region(name):
        seen.append(name)
        return real(name)

    monkeypatch.setattr(_driver._prof, "region", region)
    return seen


class _Bar:
    def update(self, t):
        pass

    def stop(self):
        pass


@pytest.mark.parametrize("lookahead", [0, 2])
def test_drive_chunks_spans_each_chunk(monkeypatch, lookahead):
    """Each confirmed chunk is drive.dispatch, then drive.wait, then
    drive.sync; with lookahead L the first L+1 dispatches run ahead and
    every confirmation is followed by the next dispatch."""
    seen = _recording(monkeypatch)
    state = drive_chunks((jnp.asarray(0.0), jnp.asarray(0, jnp.int32)),
                         lambda t, n: (t + 1.0, n + 1), te=3.5, time_index=0,
                         bar=_Bar(), retry=lambda: None, lookahead=lookahead)
    chunks = int(state[1])
    assert chunks == 4
    head = ["drive.dispatch"] * (lookahead + 1)
    cycle = ["drive.wait", "drive.sync", "drive.dispatch"]
    expect = head + cycle * (chunks - 1) + ["drive.wait", "drive.sync"]
    assert seen == expect


def test_coordinated_loop_uses_the_same_spans(monkeypatch):
    """The coordinated drive (parallel/coordinator.py) stamps the same
    three spans per chunk."""
    from pampi_tpu.parallel import coordinator as co

    seen = []
    real = prof.region

    def region(name):
        seen.append(name)
        return real(name)

    monkeypatch.setattr(co._prof, "region", region)
    state = drive_chunks((jnp.asarray(0.0), jnp.asarray(0, jnp.int32)),
                         lambda t, n: (t + 1.0, n + 1), te=2.5, time_index=0,
                         bar=_Bar(), retry=lambda: None,
                         coordinator=co.SoloCoordinator())
    assert int(state[1]) == 3
    assert seen == ["drive.dispatch", "drive.wait", "drive.sync"] * 3


def test_region_annotates_without_profile(monkeypatch):
    """PAMPI_PROFILE unset: region still enters the trace annotation (so a
    profiler trace sees the span) and records no wall time."""
    entered = []

    class Annotation:
        def __init__(self, name):
            self.name = name

        def __enter__(self):
            entered.append(self.name)

        def __exit__(self, *exc):
            return False

    monkeypatch.delenv("PAMPI_PROFILE", raising=False)
    monkeypatch.setattr(jax.profiler, "TraceAnnotation", Annotation)
    prof.reset()
    with prof.region("drive.wait"):
        pass
    assert entered == ["drive.wait"]
    assert prof.table() == {}


def test_scoped_is_metadata_only():
    """scoped() changes op_name metadata, not the computation."""
    def f(x):
        return jnp.sin(x) * 2.0

    x = jnp.arange(4.0)
    g = prof.scoped("solve", f)
    assert g.__name__ == "f"
    assert jnp.array_equal(jax.jit(g)(x), jax.jit(f)(x))
    assert '/solve/' in jax.jit(g).lower(x).as_text(debug_info=True)
