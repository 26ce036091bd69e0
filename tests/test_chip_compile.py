"""Main-path Pallas kernels compile for a described TPU v5e (no chip).

The TPU compiler refuses what interpret mode accepts: slices off the
tiling, more VMEM than a kernel may use. These tests compile each kernel
of the main path at its production width against a described v5e, so a
refusal is caught here and not on the chip. Depth is cut (n_inner 1-2,
the rows one block needs) to keep the file short; nothing runs.

The topology is described inside the fixture, never at import: only one
process may load the TPU library, and the driver's xdist workers each
import every test file (see the on-chip-measurement guide, section 2).
"""

from __future__ import annotations

import re

import jax
import jax.numpy as jnp
import pytest

F32 = jnp.float32


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache as cc
    from jax.sharding import SingleDeviceSharding

    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as exc:  # lint: allow(broad-except) — any failure to describe the chip means these tests cannot run here
        pytest.skip(f"no v5e:2x2 topology can be described here: {exc}")
    # a compile for a described chip is written to the persistent cache
    # but cannot be read back without one: keep the cache out of it. The
    # f32 kernels are built as the chip runs them, with x64 off (the
    # suite's conftest turns it on for the f64 goldens)
    prev = (jax.config.jax_enable_compilation_cache,
            jax.config.jax_enable_x64)
    jax.config.update("jax_enable_compilation_cache", False)
    jax.config.update("jax_enable_x64", False)
    cc.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", prev[0])
    jax.config.update("jax_enable_x64", prev[1])
    cc.reset_cache()


def _compile(fn, sharding, *avals, name=None):
    """Lower `fn` on shapes placed on the described chip and compile; the
    compiled HLO must hold the Mosaic kernel, as an instruction named after
    the kernel's `name=` (the name a device trace shows) when given."""
    args = [jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=sharding)
            for a in avals]
    text = jax.jit(fn).lower(*args).compile().as_text()
    assert "tpu_custom_call" in text
    if name is not None:
        assert re.search(rf"%{name}(\.\d+)? = .*tpu_custom_call", text), name
    return text


def _zeros(*shape):
    return jax.ShapeDtypeStruct(shape, F32)


def test_sor_tblock_4096_columns(one_chip):
    from pampi_tpu.ops import sor_pallas as sp

    imax, jmax = 4096, 30
    rb, br, h = sp.make_rb_iter_tblock(
        imax, jmax, 1.0 / imax, 1.0 / imax, 1.7, F32, n_inner=2,
        interpret=False)
    padded = jax.eval_shape(lambda x: sp.pad_array(x, br, h),
                            _zeros(jmax + 2, imax + 2))
    _compile(rb, one_chip, padded, padded, name="sor2d_rb_tblock")


def _canal_fluid(imax, jmax):
    """The flag field of the canal_obstacle.n2048x512 cell's geometry: a
    16 x 4 channel with one block at x 3-4, y 1.5-2.5."""
    from pampi_tpu.ops import obstacle as obst

    return obst.build_fluid(imax, jmax, 16.0 / imax, 4.0 / jmax,
                            "3.0,1.5,4.0,2.5")


def test_sor_tblock_masked_2048_columns(one_chip):
    """The flag-masked checkerboard kernel at the canal cell's whole
    geometry (2048 x 512, 4 iterations a launch: two 496-row blocks),
    under its own name."""
    from pampi_tpu.ops import sor_pallas as sp

    imax, jmax = 2048, 512
    rb, br, h = sp.make_rb_iter_tblock(
        imax, jmax, 16.0 / imax, 4.0 / jmax, 1.8, F32, n_inner=4,
        interpret=False, fluid=_canal_fluid(imax, jmax))
    padded = jax.eval_shape(lambda x: sp.pad_array(x, br, h),
                            _zeros(jmax + 2, imax + 2))
    text = _compile(rb, one_chip, padded, padded,
                    name="sor2d_rb_tblock_masked")
    assert not re.search(r"%sor2d_rb_tblock(\.\d+)? = ", text)


def test_sor_quarters_4096_columns(one_chip):
    from pampi_tpu.ops import sor_pallas as sp

    imax, jmax = 4096, 62
    rb, brq, h = sp.make_rb_iter_tblock_quarters(
        imax, jmax, 1.0 / imax, 1.0 / imax, 1.7, F32, n_inner=2,
        interpret=False)
    stacked = jax.eval_shape(lambda x: sp.pad_quarters(x, brq, h),
                             _zeros(jmax + 2, imax + 2))
    _compile(rb, one_chip, stacked, stacked, name="sor2d_rb_quarters")


def test_fused_ns2d_pre_post_4096_columns(one_chip):
    from pampi_tpu.ops import ns2d_fused as nf
    from pampi_tpu.utils.params import Parameter

    imax, jmax = 4096, 30
    param = Parameter(name="dcavity", imax=imax, jmax=jmax, re=1000.0)
    pre, post, pad, _unpad, _h = nf.make_fused_step_2d(
        param, jmax, imax, 1.0 / imax, 1.0 / jmax, F32, interpret=False)
    z = jax.eval_shape(pad, _zeros(jmax + 2, imax + 2))
    offs = jax.ShapeDtypeStruct((2,), jnp.int32)
    dt11 = _zeros(1, 1)
    _compile(pre, one_chip, offs, dt11, z, z, name="ns2d_pre")
    _compile(post, one_chip, offs, dt11, z, z, z, z, z, name="ns2d_post")


def test_fused_ns2d_pre_post_masked_2048_columns(one_chip):
    """The fused phases built with the canal cell's flag field, at its
    whole geometry."""
    from pampi_tpu.ops import ns2d_fused as nf
    from pampi_tpu.utils.params import Parameter

    imax, jmax = 2048, 512
    param = Parameter(name="canal_obstacle", imax=imax, jmax=jmax, re=100.0,
                      xlength=16.0, ylength=4.0, bcLeft=3, bcRight=3,
                      obstacles="3.0,1.5,4.0,2.5")
    pre, post, pad, _unpad, _h = nf.make_fused_step_2d(
        param, jmax, imax, 16.0 / imax, 4.0 / jmax, F32,
        fluid=jnp.asarray(_canal_fluid(imax, jmax), F32), interpret=False)
    z = jax.eval_shape(pad, _zeros(jmax + 2, imax + 2))
    offs = jax.ShapeDtypeStruct((2,), jnp.int32)
    dt11 = _zeros(1, 1)
    _compile(pre, one_chip, offs, dt11, z, z, name="ns2d_pre")
    _compile(post, one_chip, offs, dt11, z, z, z, z, z, name="ns2d_post")


def test_fused_ns3d_pre_post_128(one_chip):
    """At 128³ the PRE kernel's VMEM budget is what the chip refused
    before (ops/ns3d_fused.SCRATCH_SHARE)."""
    from pampi_tpu.ops import ns3d_fused as nf
    from pampi_tpu.utils.params import Parameter

    n = 128
    param = Parameter(name="dcavity3d", imax=n, jmax=n, kmax=n, re=1000.0)
    pre, post, pad3, _unpad3, _h = nf.make_fused_step_3d(
        param, n, n, n, 1.0 / n, 1.0 / n, 1.0 / n, F32, interpret=False)
    z = jax.eval_shape(pad3, _zeros(n + 2, n + 2, n + 2))
    offs = jax.ShapeDtypeStruct((3,), jnp.int32)
    dt11 = _zeros(1, 1)
    _compile(pre, one_chip, offs, dt11, z, z, z, name="ns3d_pre")
    _compile(post, one_chip, offs, dt11, z, z, z, z, z, z, z,
             name="ns3d_post")


def test_sor3d_octants_128(one_chip):
    from pampi_tpu.ops import sor3d_pallas as sp3

    n = 128
    rb, bk, _h = sp3.make_rb_iter_tblock_3d_octants(
        n, n, n, 1.0 / n, 1.0 / n, 1.0 / n, 1.8, F32, n_inner=1,
        interpret=False)
    stacked = jax.eval_shape(lambda x: sp3.pad_octants(x, bk, 1),
                             _zeros(n + 2, n + 2, n + 2))
    _compile(rb, one_chip, stacked, stacked, name="sor3d_rb_octants")


def test_fused_mg_cycle_refusal_pinned(one_chip):
    """The fused V-cycle does not lower for the chip on this toolchain
    (ops/mg_fused.TPU_BLOCKER keeps `tpu_mg_fused auto` on the ladder
    there). When this compile starts to pass, drop the blocker."""
    from pampi_tpu.ops import mg_fused as mf

    assert mf.TPU_BLOCKER
    levels = [(256, 256), (128, 128)]
    down, _up, plane = mf.make_cycle_kernels(
        levels, (1.0 / 256, 1.0 / 256), F32, interpret=False)
    p = _zeros(*plane)
    with pytest.raises(NotImplementedError, match="dynamic_update_slice"):
        _compile(down, one_chip, p, p)


def test_sor_qdist_shard_2048_columns(one_chip):
    """The per-shard quarters kernel of the 2x2 cell (4096² over 2x2: a
    shard 2048 wide), at two RB iterations per exchange."""
    from pampi_tpu.ops import sor_qdist as sq
    from pampi_tpu.parallel import quarters_dist as qd

    g = qd.make_qgeom(4096, 4096, 62, 2048, 2, F32)
    rb = sq.make_rb_iters_qdist(g, 1.0 / 4096, 1.0 / 4096, 1.7, F32,
                                interpret=False)
    qoffs = jax.ShapeDtypeStruct((2,), jnp.int32)
    stacked = _zeros(4, g.rp, g.w2p)
    _compile(rb, one_chip, qoffs, stacked, stacked, name="sor2d_rb_qdist")


def _assert_carried_in_place(text, kernel):
    """The while loop that calls `kernel` carries the kernel's first
    operand with no copy: its body holds no copy of that operand's shape,
    and the kernel reads the body parameter's own get-tuple-element."""
    for name in re.findall(r" while\(.*?\bbody=%([\w.\-]+)", text):
        start = text.index(f"\n%{name} (")
        body = text[start:text.index("\n}\n", start)]
        call = re.search(
            rf"%{kernel}(?:\.\d+)? = \((\w+\[[\d,]+\])\{{[^}}]*\}}, "
            r".*?custom-call\(%([\w.\-]+)", body)
        if call:
            break
    else:
        raise AssertionError(f"no while loop calls {kernel}")
    stack, operand = call.groups()
    param = re.match(rf"\n%{re.escape(name)} \(([\w.\-]+):", body).group(1)
    layout = r"\{[^}]*\}"
    assert not re.search(rf"= {re.escape(stack)}{layout} copy\(", body), \
        f"the loop copies the {stack} stack"
    assert re.search(
        rf"%{re.escape(operand)} = {re.escape(stack)}{layout} "
        rf"get-tuple-element\(%{re.escape(param)}\)", body), \
        f"{kernel} does not read the loop's own {stack} carry"


def test_sor_quarters_solve_loop_in_place_4096(one_chip, monkeypatch):
    """The flat quarters solve loop as the 4096² cell runs it (n_inner cut
    to 2): the kernel updates the carried stack in place, so XLA inserts
    no copy of it per trip."""
    from pampi_tpu.models import poisson as po

    # the solver picks interpret mode off the chip; build it as the chip
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    n = 4096
    solve = po.make_solver_fn(n, n, 1.0 / n, 1.0 / n, 1.7, 0.0, 100, F32,
                              backend="pallas", n_inner=2, layout="quarters",
                              flat=True)
    text = _compile(solve, one_chip, _zeros(n + 2, n + 2),
                    _zeros(n + 2, n + 2), name="sor2d_rb_quarters")
    _assert_carried_in_place(text, "sor2d_rb_quarters")


def test_sor3d_octants_solve_loop_in_place_128(one_chip):
    """The octant solve loop of the 128³ cell (n_inner cut to 2): the
    kernel updates the carried stack in place, so XLA inserts no copy of
    it per trip."""
    from pampi_tpu.ops import sor3d_pallas as sp3

    n = 128
    rb, bk, h = sp3.make_rb_iter_tblock_3d_octants(
        n, n, n, 1.0 / n, 1.0 / n, 1.0 / n, 1.8, F32, n_inner=2,
        interpret=False)
    solve = sp3.make_octants_solve_loop(rb, bk, h, float(n ** 3), 1e-3, 1000,
                                        n, n, n, F32)
    cube = _zeros(n + 2, n + 2, n + 2)
    text = _compile(solve, one_chip, cube, cube, name="sor3d_rb_octants")
    _assert_carried_in_place(text, "sor3d_rb_octants")
