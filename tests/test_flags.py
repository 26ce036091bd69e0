"""Runtime-flag tests: PAMPI_DEBUG / PAMPI_VERBOSE (≙ the reference's
-DDEBUG / -DVERBOSE build options, assignment-6/config.mk:72-84)."""

import os
import pathlib
import subprocess
import sys

REPO = pathlib.Path(__file__).resolve().parent.parent

POISSON_PAR = """\
name       poisson
imax       16
jmax       16
itermax    500
eps        0.001
omg        1.9
tpu_dtype  float64
"""

DCAVITY_PAR = """\
name       dcavity
imax       16
jmax       16
re         10.0
te         0.05
dt         0.02
tau        0.5
itermax    50
eps        0.001
omg        1.7
gamma      0.9
tpu_dtype  float64
"""


def _run(par_text, tmp_path, **flag):
    par = tmp_path / "run.par"
    par.write_text(par_text)
    env = {
        "PATH": f"{os.path.dirname(sys.executable)}:/usr/bin:/bin",
        "HOME": os.environ.get("HOME", "/tmp"),
        "JAX_PLATFORMS": "cpu",
        "PYTHONPATH": str(REPO),
        **flag,
    }
    proc = subprocess.run(
        [sys.executable, "-m", "pampi_tpu", str(par)],
        cwd=tmp_path, env=env, capture_output=True, text=True, timeout=300,
    )
    assert proc.returncode == 0, proc.stdout + proc.stderr
    return proc.stdout


def test_debug_prints_per_iteration_residuals(tmp_path):
    out = _run(POISSON_PAR, tmp_path, PAMPI_DEBUG="1")
    lines = [l for l in out.splitlines() if "Residuum:" in l]
    # "<it> Residuum: <res>", 0-based, one per iteration, count == printed it
    assert lines and lines[0].split()[0] == "0"
    it = int(out.split("Walltime")[0].split()[-1])
    assert len(lines) == it
    assert int(lines[-1].split()[0]) == it - 1


def test_debug_off_prints_nothing(tmp_path):
    out = _run(POISSON_PAR, tmp_path)
    assert "Residuum:" not in out


def _parse_time_lines(out):
    """-> [(TIME, TIMESTEP)] from 'TIME <t> , TIMESTEP <dt>' lines."""
    lines = [l for l in out.splitlines() if l.startswith("TIME ")]
    return [(float(l.split()[1]), float(l.split()[4])) for l in lines]


def _assert_time_is_post_increment(pairs):
    """The reference prints TIME after `t += dt` (A5 main.c:52-57,
    A6 main.c:58-62): line i carries the cumulative sum of TIMESTEPs
    through step i — never a leading 0.0."""
    acc = 0.0
    for time_v, dt_v in pairs:
        acc += dt_v
        assert abs(time_v - acc) < 1e-9, (time_v, acc)


def test_verbose_prints_time_per_step_and_no_progress_bar(tmp_path):
    out = _run(DCAVITY_PAR, tmp_path, PAMPI_VERBOSE="1")
    lines = [l for l in out.splitlines() if l.startswith("TIME ")]
    assert lines and ", TIMESTEP " in lines[0]
    _assert_time_is_post_increment(_parse_time_lines(out))
    assert "[" not in out.split("Solution took")[0].split("omega")[-1]


def test_verbose_off_shows_progress_bar(tmp_path):
    out = _run(DCAVITY_PAR, tmp_path)
    assert "TIME " not in out
    assert "[" in out  # the 10-segment progress bar rendered


def test_flags_work_distributed(tmp_path):
    # 8-device virtual mesh (tpu_mesh auto): rank-0 shard prints once per
    # convergence check / step — no per-shard duplication
    import os

    extra = {
        "XLA_FLAGS": "--xla_force_host_platform_device_count=8",
        "PAMPI_DEBUG": "1",
        "PAMPI_VERBOSE": "1",
    }
    out = _run(DCAVITY_PAR.replace("imax       16", "imax       16")
               + "tpu_mesh   auto\n", tmp_path, **extra)
    res_lines = [l for l in out.splitlines() if "Residuum:" in l]
    time_lines = [l for l in out.splitlines() if l.startswith("TIME ")]
    assert res_lines and time_lines
    # rank-0-only: TIME lines are unique (no 8x duplicates)
    assert len(time_lines) == len(set(time_lines))
    _assert_time_is_post_increment(_parse_time_lines(out))


def test_xla_cache_enable_and_disable(monkeypatch, tmp_path):
    import jax

    from pampi_tpu.utils import xlacache

    prev = jax.config.jax_compilation_cache_dir
    tracebacks = jax.config.jax_include_full_tracebacks_in_locations
    try:
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path / "c"))
        assert xlacache.enable() == str(tmp_path / "c")
        assert (tmp_path / "c").is_dir()
        assert jax.config.jax_compilation_cache_dir == str(tmp_path / "c")
        # one program, one cache key, whatever the call stack
        assert not jax.config.jax_include_full_tracebacks_in_locations
        jax.config.update("jax_compilation_cache_dir", prev)
        jax.config.update("jax_enable_compilation_cache", False)
        assert xlacache.enable() is None
        # disabled means the config was left untouched
        assert jax.config.jax_compilation_cache_dir == prev
    finally:
        jax.config.update("jax_enable_compilation_cache", True)
        jax.config.update("jax_compilation_cache_dir", prev)
        jax.config.update("jax_include_full_tracebacks_in_locations",
                          tracebacks)


def test_xla_cache_dir_unset_resolves_to_checkout(monkeypatch):
    """Unset JAX_COMPILATION_CACHE_DIR: an accelerator run caches in the
    fixed <checkout>/.jax_cache (never a home, tmp or pid path) and a
    CPU run stays uncached."""
    import os

    from pampi_tpu.utils import xlacache

    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    assert xlacache.cache_dir("tpu") == os.path.join(repo, ".jax_cache")
    assert xlacache.cache_dir("cpu") is None
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "/some/dir")
    assert xlacache.cache_dir("tpu") == xlacache.cache_dir("cpu") \
        == "/some/dir"


DCAVITY3D_PAR = """\
name       dcavity3d
imax       32
jmax       32
kmax       32
re         1000.0
te         0.02
dt         0.02
tau        0.5
itermax    1000
eps        0.001
omg        1.8
gamma      0.9
tpu_dtype  float64
tpu_mesh   1
"""


def test_verbose_prints_solver_config_block_3d(tmp_path):
    """PAMPI_VERBOSE on a 3-D run emits the reference's printConfig block
    (A6 solver.c:36-73) with COMPUTED values matching the captured
    reference-run log (tests/fixtures/dc3b.log: same 32^3 dcavity grid)."""
    out = _run(DCAVITY3D_PAR, tmp_path, PAMPI_VERBOSE="1")
    assert "Parameters for #dcavity3d#" in out
    assert "\tCell size (dx, dy, dz): 0.031250, 0.031250, 0.031250" in out
    assert "\tdt bound: 0.162760" in out  # 0.5*Re/(3/dx^2), the fixture value
    _assert_time_is_post_increment(_parse_time_lines(out))
    # and not there without the flag
    out2 = _run(DCAVITY3D_PAR, tmp_path)
    assert "Parameters for #" not in out2
