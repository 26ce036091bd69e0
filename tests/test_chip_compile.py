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


def _compile(fn, sharding, *avals):
    """Lower `fn` on shapes placed on the described chip and compile; the
    compiled HLO must hold the Mosaic kernel."""
    args = [jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=sharding)
            for a in avals]
    text = jax.jit(fn).lower(*args).compile().as_text()
    assert "tpu_custom_call" in text
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
    _compile(rb, one_chip, padded, padded)


def test_sor_quarters_4096_columns(one_chip):
    from pampi_tpu.ops import sor_pallas as sp

    imax, jmax = 4096, 62
    rb, brq, h = sp.make_rb_iter_tblock_quarters(
        imax, jmax, 1.0 / imax, 1.0 / imax, 1.7, F32, n_inner=2,
        interpret=False)
    stacked = jax.eval_shape(lambda x: sp.pad_quarters(x, brq, h),
                             _zeros(jmax + 2, imax + 2))
    _compile(rb, one_chip, stacked, stacked)


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
    _compile(pre, one_chip, offs, dt11, z, z)
    _compile(post, one_chip, offs, dt11, z, z, z, z, z)


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
    _compile(pre, one_chip, offs, dt11, z, z, z)
    _compile(post, one_chip, offs, dt11, z, z, z, z, z, z, z)


def test_sor3d_octants_128(one_chip):
    from pampi_tpu.ops import sor3d_pallas as sp3

    n = 128
    rb, bk, _h = sp3.make_rb_iter_tblock_3d_octants(
        n, n, n, 1.0 / n, 1.0 / n, 1.0 / n, 1.8, F32, n_inner=1,
        interpret=False)
    stacked = jax.eval_shape(lambda x: sp3.pad_octants(x, bk, 1),
                             _zeros(n + 2, n + 2, n + 2))
    _compile(rb, one_chip, stacked, stacked)


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
