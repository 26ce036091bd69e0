"""Tracecheck (pampi_tpu/analysis/ + tools/lint.py) — ISSUE 5/6 acceptance:

- AST LINT: the tree is clean; every rule fires on a seeded violation
  with a file:line diagnostic; `# lint: allow(<rule>)` escapes it.
- HALO FOOTPRINTS: the production registry passes and the CA entries are
  TIGHT (measured == declared, so the probe is sharp, not vacuous); the
  two mutation classes — a seeded under-halo declaration and an
  over-wide stencil — are both flagged; the FUSE_CHAIN slack is pinned.
- JAXPR CONTRACTS: a config subset round-trips through the baseline
  (update -> check clean -> update again byte-stable); seeded
  launch-count drift and hash drift are flagged with primitive-count
  diffs; the committed CONTRACTS.json matches the harness environment
  and the current config matrix.
- COMM CONTRACTS (ISSUE 6): the collective census round-trips
  byte-stable through the comm baseline; a smuggled extra exchange, a
  byte-volume drift, and a resharding collective are each flagged with
  per-primitive diffs; the telemetry halo record cross-check fires on a
  mis-priced record and on a dropped deep-exchange message.
- PALLAS RESOURCES (ISSUE 6): the traced matrix + large-grid kernel
  builds are clean; an over-budget VMEM block, an OOB index map, a
  mistiled partitioned block, and both aliasing hazards are each
  flagged with the kernel's file:line.

Compile cost: everything here TRACES (make_jaxpr) or linearizes tiny
blocks — no jit execution of solver chunks.
"""

import json
import os
import subprocess
import sys
import types

import pytest

from pampi_tpu.analysis import (astlint, commcheck, halocheck, jaxprcheck,
                                palcheck)

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


# ---------------------------------------------------------------------------
# astlint
# ---------------------------------------------------------------------------

def test_astlint_tree_clean():
    """The repo itself passes its own lint (the make-lint gate)."""
    violations, errors = astlint.lint_tree(REPO)
    assert errors == []
    assert violations == [], "\n".join(str(v) for v in violations)


def _lint_src(tmp_path, src, name="pampi_tpu/models/seeded.py", rules=None):
    path = tmp_path / name
    path.parent.mkdir(parents=True, exist_ok=True)
    path.write_text(src)
    vs, err = astlint.lint_file(str(path), rules=rules,
                                root=str(tmp_path))
    assert err is None
    return vs


def test_rogue_env_read_flagged(tmp_path):
    """The satellite bug class (PAMPI_CSV in dmvm, PAMPI_PROFILE cached at
    import): any os.environ read outside utils/flags.py is flagged at its
    line; the allow escape and the accessor home are exempt."""
    src = ("import os\n"
           "MODE = os.environ.get('PAMPI_X', '0')\n"
           "PATH = os.environ['PAMPI_Y']\n"
           "OK = os.environ.get('PAMPI_Z')  # lint: allow(env-read) — t\n")
    vs = _lint_src(tmp_path, src)
    assert [(v.line, v.rule) for v in vs] == [
        (2, "env-read"), (3, "env-read")]
    assert "flags.env()" in vs[0].message
    # the accessor layer itself is exempt by location
    vs = _lint_src(tmp_path, src, name="pampi_tpu/utils/flags.py")
    assert vs == []


def test_raw_shard_map_flagged(tmp_path):
    """The two-past-PRs rule: shard_map only through compat_shard_map —
    in EVERY spelling (qualified call, bare call after `from jax import
    shard_map`, aliased module import). Applies to harness trees too
    (tools/ and tests/ regressed before)."""
    src = ("import jax\n"
           "from jax._src.shard_map import shard_map\n"
           "f = jax.shard_map(lambda x: x, None, None, None)\n")
    vs = _lint_src(tmp_path, src, name="tools/seeded_tool.py")
    assert [v.line for v in vs] == [2, 3]
    assert all(v.rule == "raw-shard-map" for v in vs)
    assert "compat_shard_map" in vs[0].message
    vs = _lint_src(tmp_path, src, name="pampi_tpu/parallel/comm.py")
    assert vs == []  # the shim's home
    # ...but NOT a file that merely ends with the shim's name (path-
    # component boundary, never a bare suffix)
    vs = _lint_src(tmp_path, src, name="pampi_tpu/parallel/webcomm.py")
    assert len(vs) == 2

    # the newer-jax spelling and the aliased import both flag too
    src2 = ("from jax import shard_map\n"
            "import jax._src.shard_map as sm\n"
            "a = shard_map(lambda x: x, None, None, None)\n"
            "b = sm.shard_map(lambda x: x, None, None, None)\n")
    vs = _lint_src(tmp_path, src2, name="tools/seeded_tool2.py")
    assert [v.line for v in vs] == [1, 2, 3, 4]
    assert all(v.rule == "raw-shard-map" for v in vs)


def test_traced_context_rules(tmp_path):
    """np.* and nondeterminism inside a traced closure (a def nested in a
    _build_*/make_* builder); builder BODIES are trace-time host code
    where numpy is legitimate."""
    src = ("import numpy as np\n"
           "import time, random\n"
           "def make_step(n):\n"
           "    c = np.arange(n)  # builder body: constant baking, legal\n"
           "    def step(x):\n"
           "        y = np.asarray(x)\n"
           "        t = time.time()\n"
           "        r = random.random()\n"
           "        return y + c[0] + t + r\n"
           "    return step\n")
    vs = _lint_src(tmp_path, src)
    assert [(v.line, v.rule) for v in vs] == [
        (6, "np-in-traced"), (7, "traced-nondet"), (8, "traced-nondet")]


def test_broad_except_and_print(tmp_path):
    src = ("def f():\n"
           "    try:\n"
           "        pass\n"
           "    except Exception:\n"
           "        print('boom')\n"
           "    except Exception:  # lint: allow(broad-except) — probe\n"
           "        pass\n")
    vs = _lint_src(tmp_path, src)
    assert [(v.line, v.rule) for v in vs] == [
        (4, "broad-except"), (5, "print-call")]
    assert str(vs[0]).startswith("pampi_tpu/models/seeded.py:4: ")


def test_env_inventory_complete():
    """The static env-var inventory: every PAMPI_* knob the library reads
    is registered through flags.env at a named site — the rogue reads the
    satellites fixed (PAMPI_CSV, PAMPI_PROFILE) now appear here. The
    RUNTIME registry (flags.registered(), populated as accessors run)
    must agree with the static scan: a var the process actually read that
    the scan can't see would mean a non-literal name snuck past the
    lint."""
    inv = astlint.env_inventory(REPO)
    for var, home in [
        ("PAMPI_TELEMETRY", "utils/telemetry.py"),
        ("PAMPI_FAULTS", "utils/faultinject.py"),
        ("PAMPI_PROFILE", "utils/profiling.py"),
        ("PAMPI_CSV", "models/dmvm.py"),
        ("JAX_COMPILATION_CACHE_DIR", "utils/xlacache.py"),
        ("PAMPI_NATIVE", "utils/native.py"),
        ("PAMPI_COORDINATOR", "parallel/multihost.py"),
    ]:
        assert var in inv, var
        assert any(home in site for site in inv[var]), (var, inv[var])

    from pampi_tpu.utils import faultinject as fi
    from pampi_tpu.utils import flags, profiling, telemetry

    telemetry.enabled()
    fi.enabled()
    profiling.enabled()
    reg = flags.registered()
    assert {"PAMPI_TELEMETRY", "PAMPI_FAULTS", "PAMPI_PROFILE"} <= set(reg)
    assert set(reg) <= set(inv) | {"PAMPI_DEBUG", "PAMPI_VERBOSE",
                                   "PAMPI_CHECK", "PAMPI_DTYPE"}
    # accessor docs ride the registry (the runtime-readable knob table)
    assert reg["PAMPI_TELEMETRY"]


# ---------------------------------------------------------------------------
# halocheck
# ---------------------------------------------------------------------------

def _ca_entry(n=1, ragged=False):
    return halocheck._ca2d_entry(n, ragged=ragged)


def test_halo_registry_subset_clean_and_tight():
    """The CA contracts hold AND are tight: ca_halo(n) layers are exactly
    consumed (divisible 2n; ragged 2n+1 — the dead-shard wall-ghost
    refresh), so the probe measures the real footprint, not a lower
    bound."""
    for n, ragged in ((1, False), (2, False), (1, True)):
        e = _ca_entry(n, ragged)
        assert halocheck.check_entry(e) == []
        assert max(halocheck.measure(e).values()) == e.declared, e.name
    post = halocheck._post2d_entry()
    assert halocheck.check_entry(post) == []
    assert halocheck.measure(post)[2] == 1  # p: exactly the halo-1 ring


def test_halo_under_declaration_flagged():
    """Mutation 1 (the seeded too-narrow halo): the same kernel declared
    one layer shallower is an under-halo read, with a file:line anchor at
    the kernel source."""
    e = _ca_entry(2)
    e.declared -= 1
    vs = halocheck.check_entry(e)
    assert len(vs) == 1
    v = vs[0]
    assert v.rule == "halo-footprint"
    assert "stencil2d.py" in v.path and v.line > 0
    assert "4 cells beyond" in v.message and "declared halo is 3" in v.message


def test_halo_overwide_stencil_flagged():
    """Mutation 2 (the seeded too-wide stencil offset): a ±2 read smuggled
    into the n=1 iteration — the regression class where someone widens a
    difference operator without bumping ca_halo. Built on a block with
    spare layers (halo 4) so the wider read has real cells to land on;
    the declaration stays the production ca_halo(1) = 2."""
    import jax.numpy as jnp

    from pampi_tpu.parallel import stencil2d as s2

    jl = il = 6
    room = 4  # block layers available; the CONTRACT stays ca_halo(1) = 2
    masks = s2.ca_masks(jl, il, room, 30, 30, float, joff=8, ioff=8)
    shape = (jl + 2 * room, il + 2 * room)

    def base(p, rhs):
        return s2.ca_rb_iters(p, rhs, 1, masks, 0.45, 1.0, 1.3)[0]

    entry = halocheck.HaloEntry(
        name="mutated.ca_rb_iters", fn=base,
        in_shapes=(shape, shape),
        owned=(slice(room, room + jl), slice(room, room + il)),
        declared=s2.ca_halo(1),
        anchor=("mutated.py", 1))
    assert halocheck.check_entry(entry) == []  # the clean tree passes

    def widened(p, rhs):
        return base(p + 0.001 * jnp.roll(p, 2, axis=0), rhs)

    entry.fn = widened
    vs = halocheck.check_entry(entry)
    assert len(vs) == 1
    assert "4 cells beyond the owned region" in vs[0].message
    assert "declared halo is 2" in vs[0].message


def test_halo_fused_pre_within_budget():
    """The fused PRE chain stays within FUSE_CHAIN on every shard
    position (the deep-halo PRE contract)."""
    for shard in ("interior", "corner_lo", "wall_hi"):
        e = halocheck._pre2d_entry(shard)
        assert halocheck.check_entry(e) == [], shard


def test_fuse_chain_slack_pinned():
    """The ROADMAP carried-forward shrink, landed and pinned: the
    MEASURED PRE-chain footprint (2) now IS the declaration
    (`FUSE_FOOTPRINT`), and the deep exchange ships exactly
    footprint + 1 (`FUSE_DEEP_HALO = 3`, down from the conservative
    FUSE_CHAIN + 1 = 4) — zero slack. If the chain ever widens, the
    re-derivation here AND halocheck's PRE entries (declared =
    FUSE_FOOTPRINT) fail before any distributed run corrupts."""
    from pampi_tpu.ops import ns2d_fused as nf

    measured = halocheck.pre_chain_footprint()
    assert measured == nf.FUSE_FOOTPRINT == 2, (
        "PRE-chain footprint moved — re-audit FUSE_DEEP_HALO/OVERLAP_RIM "
        "and re-run dist parity + make lint-update")
    assert nf.FUSE_CHAIN == 3  # the stage-count budget, documentation
    assert nf.FUSE_DEEP_HALO == nf.FUSE_FOOTPRINT + 1 == 3
    assert nf.OVERLAP_RIM == nf.FUSE_FOOTPRINT + 1 == 3


# ---------------------------------------------------------------------------
# jaxprcheck
# ---------------------------------------------------------------------------

def _subset():
    keep = {"ns2d_jnp", "ns2d_fused_fft", "ns2d_fused_fold"}
    return [c for c in jaxprcheck.standard_configs() if c.name in keep]


@pytest.fixture(scope="module")
def subset_baseline():
    """One traced subset baseline shared by the drift tests (each config
    build is a solver construction — don't pay it per test)."""
    vs, fresh = jaxprcheck.run(baseline=None, configs=_subset(),
                               update=True)
    assert vs == []
    return fresh


def test_contracts_roundtrip_stable(subset_baseline):
    """update -> check clean -> update again byte-stable (the --update
    round-trip contract: regenerating without a code change is a no-op
    diff)."""
    vs, _ = jaxprcheck.run(baseline=subset_baseline, configs=_subset())
    assert vs == [], [str(v) for v in vs]
    _, again = jaxprcheck.run(baseline=subset_baseline, configs=_subset(),
                              update=True)
    assert json.dumps(again, sort_keys=True) == json.dumps(
        subset_baseline, sort_keys=True)


def test_seeded_launch_drift_flagged(subset_baseline):
    """Mutation: a baseline pinning a different launch count (as if a
    layout pass crept back between the fused kernels) fails with the
    dispatch decision in the diagnostic."""
    tampered = json.loads(json.dumps(subset_baseline))
    tampered["configs"]["ns2d_fused_fft"]["pallas_calls"] = 4
    cfg = [c for c in _subset() if c.name == "ns2d_fused_fft"]
    vs, _ = jaxprcheck.run(baseline=tampered, configs=cfg)
    launch = [v for v in vs if v.rule == "launch-count"]
    assert len(launch) == 1
    assert "4 -> 2" in launch[0].message
    assert launch[0].path.endswith("models/ns2d.py")


def test_seeded_hash_drift_flagged(subset_baseline):
    """Mutation: hash drift (an eqn-level change to the flag-off program)
    fails with a primitive-count diff of the offending eqns."""
    tampered = json.loads(json.dumps(subset_baseline))
    entry = tampered["configs"]["ns2d_jnp"]
    entry["hash"] = "0" * 64
    entry["prims"] = dict(entry["prims"], pallas_call=7, while_loop_x=1)
    cfg = [c for c in _subset() if c.name == "ns2d_jnp"]
    vs, _ = jaxprcheck.run(baseline=tampered, configs=cfg)
    drift = [v for v in vs if v.rule == "trace-drift"]
    assert len(drift) == 1
    msg = drift[0].message
    assert "pallas_call: 7 -> 0" in msg and "while_loop_x: 1 -> 0" in msg
    assert "--update" in msg


def test_env_mismatch_reported_not_compared(subset_baseline):
    """A baseline from another toolchain reports environment drift once
    and skips hash comparison instead of failing every config."""
    foreign = json.loads(json.dumps(subset_baseline))
    foreign["env"] = dict(foreign["env"], jax="9.9.9")
    for e in foreign["configs"].values():
        e["hash"] = "f" * 64   # would fail if compared
        e["pallas_calls"] = 9  # likewise toolchain-dependent: not compared
    vs, _ = jaxprcheck.run(baseline=foreign, configs=_subset())
    assert [v.rule for v in vs] == ["trace-drift"]
    assert "environment" in vs[0].message


def test_callback_and_dtype_detectors():
    """The primitive scanners behind the host-callback and dtype checks."""
    import jax
    import jax.numpy as jnp

    def noisy(x):
        jax.debug.print("x={}", x)
        return x * 2.0

    jx = jax.make_jaxpr(noisy)(1.0)
    assert jaxprcheck.host_callbacks(jx.jaxpr) == ["debug_print"]

    def promoting(x):
        return x.astype(jnp.float64) + 1.0, x * jnp.float32(2)

    jx = jax.make_jaxpr(promoting)(jnp.zeros((3,), jnp.float32))
    fts = jaxprcheck.float_dtypes(jx.jaxpr)
    assert {"float32", "float64"} <= fts


def test_telemetry_arity_contract(tmp_path, monkeypatch):
    """With PAMPI_TELEMETRY armed the traced chunk and initial_state()
    agree at the metrics arity (6/6) and the signature reflects it — the
    contract every measurement tool leans on."""
    from pampi_tpu.models.ns2d import NS2DSolver
    from pampi_tpu.utils import telemetry as tm
    from pampi_tpu.utils.params import Parameter

    monkeypatch.setenv("PAMPI_TELEMETRY", str(tmp_path / "t.jsonl"))
    tm.reset()
    s = NS2DSolver(Parameter(name="dcavity", imax=16, jmax=16, re=10.0,
                             te=0.02, tau=0.5, itermax=10, eps=1e-4))
    sig = jaxprcheck.chunk_signature(s)
    assert sig["state_arity"] == sig["invars"] == sig["outvars"] == 6
    tm.reset()


def test_committed_baseline_current():
    """The committed CONTRACTS.json was generated in THIS harness
    environment and covers exactly the current config matrix — a stale
    baseline (config added/renamed without --update) fails here, not on
    an operator's machine."""
    path = os.path.join(REPO, "CONTRACTS.json")
    with open(path) as fh:
        baseline = json.load(fh)
    assert baseline["env"] == jaxprcheck.environment()
    assert set(baseline["configs"]) == {
        c.name for c in jaxprcheck.standard_configs()}
    # the comm census covers the SAME matrix (ISSUE 6: the comm baseline
    # is committed, not optional)
    assert set(baseline["comm"]) == set(baseline["configs"])
    for entry in baseline["comm"].values():
        assert set(entry) >= {"collectives", "ppermute_bytes", "strips",
                              "halo"}
    # so does the precision census (ISSUE 20: the cast contract is
    # committed alongside)
    assert set(baseline["precision"]) == set(baseline["configs"])
    for entry in baseline["precision"].values():
        assert set(entry) >= {"dtype", "float_dtypes", "casts",
                              "narrowing", "reductions"}
    # and it passes the shared artifact lint (the one import spelling the
    # other suites use — don't load the module under a second name)
    from tools import check_artifact as ca

    assert ca.lint_contracts(baseline) == []
    assert ca.lint_contracts({"version": 1}) != []
    # a truncated comm section is a lint error, not a silent no-op
    broken = json.loads(json.dumps(baseline))
    broken["comm"].popitem()
    assert any(".comm" in e for e in ca.lint_contracts(broken))
    broken2 = json.loads(json.dumps(baseline))
    next(iter(broken2["comm"].values())).pop("ppermute_bytes")
    assert any("ppermute_bytes" in e for e in ca.lint_contracts(broken2))


def test_lint_driver_ast_pass():
    """tools/lint.py --only ast runs standalone (no jax import needed for
    the rule pass) and exits clean on the tree — and on an explicit file
    path (the per-file pre-commit invocation)."""
    proc = subprocess.run(
        [sys.executable, os.path.join(REPO, "tools", "lint.py"),
         "--only", "ast"],
        capture_output=True, text=True, cwd=REPO)
    assert proc.returncode == 0, proc.stdout + proc.stderr
    assert "[ast] ok" in proc.stdout
    proc = subprocess.run(
        [sys.executable, os.path.join(REPO, "tools", "lint.py"),
         "--only", "ast", "pampi_tpu/utils/flags.py"],
        capture_output=True, text=True, cwd=REPO)
    assert proc.returncode == 0, proc.stdout + proc.stderr
    assert "[ast] ok" in proc.stdout


def test_lint_driver_only_multiselect():
    """--only takes a comma list (the ISSUE 6 satellite: the overlap
    refactor's inner loop runs `--only comm` alone; `ast,artifacts` here
    keeps the test jax-trace-free), runs passes in CANONICAL order
    regardless of the flag's spelling (artifacts must follow a pending
    --update flush), and rejects unknown pass names."""
    proc = subprocess.run(
        [sys.executable, os.path.join(REPO, "tools", "lint.py"),
         "--only", "artifacts,ast"],
        capture_output=True, text=True, cwd=REPO)
    assert proc.returncode == 0, proc.stdout + proc.stderr
    assert "[ast] ok" in proc.stdout
    assert "[artifacts] ok" in proc.stdout
    assert proc.stdout.index("[ast]") < proc.stdout.index("[artifacts]")
    proc = subprocess.run(
        [sys.executable, os.path.join(REPO, "tools", "lint.py"),
         "--only", "ast,nonsense"],
        capture_output=True, text=True, cwd=REPO)
    assert proc.returncode == 2
    assert "nonsense" in proc.stderr


def test_lint_partial_update_no_mixed_env_baseline(tmp_path, monkeypatch,
                                                  comm_traced):
    """A partial `--update` (comm section only) under a CHANGED trace
    environment must not pair the new `env` key with configs hashes
    traced under the old one — the driver regenerates the missing
    section from the shared matrix instead of preserving it."""
    sys.path.insert(0, os.path.join(REPO, "tools"))
    try:
        import lint as lint_mod
    finally:
        sys.path.pop(0)

    _, configs_fresh = jaxprcheck.run(traced=comm_traced, update=True)
    _, comm_fresh = commcheck.run(traced=comm_traced, update=True)
    stale = dict(configs_fresh, comm=comm_fresh)
    stale["env"] = dict(stale["env"], jax="0.0.0")  # another toolchain
    path = tmp_path / "CONTRACTS.json"
    path.write_text(json.dumps(stale))

    ctx = lint_mod.TraceContext(str(path), update=True)
    ctx._traced = comm_traced  # the subset matrix, already built
    vs = ctx.run_comm()
    assert vs == []
    assert ctx.fresh_configs is None  # only the comm pass ran
    ctx.write()
    merged = json.loads(path.read_text())
    assert merged["env"] == jaxprcheck.environment()
    # configs were REGENERATED under the new env, not carried over
    assert merged["configs"] == configs_fresh["configs"]
    # and a full check against the result is clean
    vs, _ = jaxprcheck.run(baseline=merged, traced=comm_traced)
    assert vs == [], [str(v) for v in vs]


# ---------------------------------------------------------------------------
# commcheck
# ---------------------------------------------------------------------------

@pytest.fixture(scope="module")
def comm_traced():
    """One traced subset shared by the comm/pallas suites (each config is
    a solver build — don't pay it per test): a single-device chunk, a
    jnp dist chunk, and a fused dist chunk (deep exchange + both fused
    kernels)."""
    keep = {"ns2d_jnp", "ns2d_dist_jnp", "ns2d_dist_fused"}
    cfgs = [c for c in jaxprcheck.standard_configs() if c.name in keep]
    return jaxprcheck.trace_matrix(cfgs)


def _fused(traced):
    return next(t for t in traced if t.cfg.name == "ns2d_dist_fused")


def test_comm_roundtrip_stable(comm_traced):
    """update -> check clean -> update again byte-stable (the comm
    section --update contract, the ISSUE 6 satellite)."""
    vs, fresh = commcheck.run(traced=comm_traced, update=True)
    assert vs == [], [str(v) for v in vs]
    vs, _ = commcheck.run(baseline=fresh, traced=comm_traced)
    assert vs == [], [str(v) for v in vs]
    _, again = commcheck.run(traced=comm_traced, update=True)
    assert json.dumps(again, sort_keys=True) == json.dumps(
        fresh, sort_keys=True)


def test_comm_extra_collective_flagged(comm_traced):
    """Mutation 1: a baseline recording fewer exchanges (as if the
    current tree smuggled extras in) fails with a per-primitive diff —
    and a byte drift with a per-strip diff."""
    _, fresh = commcheck.run(traced=comm_traced, update=True)
    tampered = json.loads(json.dumps(fresh))
    entry = tampered["ns2d_dist_fused"]
    entry["collectives"]["ppermute"] -= 2
    vs, _ = commcheck.run(baseline=tampered, traced=comm_traced)
    count = [v for v in vs if v.rule == commcheck.RULE_COUNT]
    assert len(count) == 1
    assert "ppermute: 18 -> 20 (+2)" in count[0].message
    assert count[0].path.endswith("models/ns2d_dist.py")

    tampered = json.loads(json.dumps(fresh))
    entry = tampered["ns2d_dist_fused"]
    entry["ppermute_bytes"] -= 1024
    entry["strips"]["3x14:float64"] -= 1
    vs, _ = commcheck.run(baseline=tampered, traced=comm_traced)
    bytes_vs = [v for v in vs if v.rule == commcheck.RULE_BYTES]
    assert len(bytes_vs) == 1
    assert "3x14:float64: 3 -> 4 (+1)" in bytes_vs[0].message


def test_comm_smuggled_exchange_census():
    """Mutation 2, on a real program pair: the same shard_map stencil
    body with a DUPLICATED halo_exchange censuses to exactly double the
    ppermute count/bytes, and checking the doubled program against the
    clean baseline fails both rules."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import PartitionSpec as P

    from pampi_tpu.parallel.comm import CartComm, halo_exchange

    comm = CartComm(ndims=2, dims=(2, 2))
    spec = P("j", "i")

    def once(x):
        return halo_exchange(x, comm)

    def twice(x):
        return halo_exchange(halo_exchange(x, comm), comm)

    x = jnp.zeros((16, 16))
    jx1 = jax.make_jaxpr(comm.shard_map(once, (spec,), spec))(x)
    jx2 = jax.make_jaxpr(comm.shard_map(twice, (spec,), spec))(x)
    c1, c2 = commcheck.census(jx1.jaxpr), commcheck.census(jx2.jaxpr)
    assert c1["collectives"]["ppermute"] == 4  # 2 axes x 2 directions
    assert c2["collectives"]["ppermute"] == 8
    assert c2["ppermute_bytes"] == 2 * c1["ppermute_bytes"] > 0

    clean = dict(c1, halo=None)
    mutant = types.SimpleNamespace(
        cfg=types.SimpleNamespace(name="mutated", family="ns2d_dist",
                                  dims=(2, 2)),
        solver=object(), jaxpr=jx2)
    vs, _ = commcheck.check_config(mutant, clean, env_matches=True)
    rules = {v.rule for v in vs}
    assert commcheck.RULE_COUNT in rules and commcheck.RULE_BYTES in rules
    assert any("ppermute: 4 -> 8 (+4)" in v.message for v in vs)


def test_comm_reshard_flagged():
    """A resharding collective (what sharding propagation inserts behind
    an explicit schedule) is banned outright — no baseline needed."""
    import jax
    import jax.numpy as jnp
    from jax import lax
    from jax.sharding import PartitionSpec as P

    from pampi_tpu.parallel.comm import CartComm

    comm = CartComm(ndims=2, dims=(2, 2))

    def gathers(x):
        return lax.all_gather(x, "j")

    jx = jax.make_jaxpr(
        comm.shard_map(gathers, (P("j", "i"),), P(None, "j", "i"))
    )(jnp.zeros((16, 16)))
    bad = types.SimpleNamespace(
        cfg=types.SimpleNamespace(name="reshard", family="ns2d_dist",
                                  dims=(2, 2)),
        solver=object(), jaxpr=jx)
    vs, _ = commcheck.check_config(bad, None, env_matches=True)
    assert [v.rule for v in vs] == [commcheck.RULE_RESHARD]
    assert "all_gather" in vs[0].message


def test_comm_single_device_collective_flagged(comm_traced):
    """A collective in a single-device chunk means a mesh axis leaked —
    the census of a dist program checked under a dims=None config
    fails."""
    dist = _fused(comm_traced)
    leaked = types.SimpleNamespace(
        cfg=types.SimpleNamespace(name="leaked", family="ns2d",
                                  dims=None),
        solver=object(), jaxpr=dist.jaxpr)
    vs, _ = commcheck.check_config(leaked, None, env_matches=True)
    assert any(v.rule == commcheck.RULE_COUNT
               and "single-device" in v.message for v in vs)


def test_comm_telemetry_crosscheck(comm_traced):
    """The halo-record cross-check: the solver's own static accounting
    (a) prices exactly what comm.halo_exchange_bytes says, (b) declares
    deep-exchange messages the trace really contains — and a mis-priced
    record or a dropped/duplicated deep strip is flagged."""
    t = _fused(comm_traced)
    entry = commcheck.config_entry(t)
    rec = t.solver._halo_record()
    assert commcheck.crosscheck_record(rec, entry) == []

    # (a) a record hand-computing bytes (off by one strip) is caught
    bad = dict(rec, deep_exchange_bytes=rec["deep_exchange_bytes"] - 64)
    errs = commcheck.crosscheck_record(bad, entry)
    assert any("deep_exchange_bytes" in e for e in errs)

    # (b) a trace missing one declared deep message is caught (exact
    # count for the deep class: a duplicated exchange can't hide either)
    thin = json.loads(json.dumps(entry))
    thin["strips"]["3x14:float64"] -= 1
    errs = commcheck.crosscheck_record(rec, thin)
    assert any("deep-exchange strip" in e for e in errs)


def test_comm_halo_record_is_shared_accounting(comm_traced):
    """The ISSUE 6 dedupe satellite: the PR 3 telemetry `halo` record and
    commcheck both price through parallel/comm.halo_exchange_bytes — the
    solver hook returns the SAME dict the telemetry plane emits, and the
    utils/telemetry spelling is an alias of the comm helper."""
    import numpy as np

    from pampi_tpu.parallel.comm import (halo_exchange_bytes,
                                         halo_strip_shapes)
    from pampi_tpu.utils import telemetry as tm

    rec = _fused(comm_traced).solver._halo_record()
    isz = np.dtype(rec["dtype"]).itemsize
    shard = tuple(rec["shard"])
    assert rec["exchange_bytes_depth1"] == halo_exchange_bytes(
        shard, 1, isz)
    assert rec["deep_exchange_bytes"] == halo_exchange_bytes(
        shard, rec["deep_halo"], isz)
    # the alias and the helper agree (and the strip geometry sums to it)
    assert tm.halo_exchange_bytes((8, 16), 1, 4) == halo_exchange_bytes(
        (8, 16), 1, 4)
    strips = halo_strip_shapes(shard, rec["deep_halo"])
    total = sum(2 * int(np.prod(s)) for s in strips) * isz
    assert total == rec["deep_exchange_bytes"]


# ---------------------------------------------------------------------------
# palcheck
# ---------------------------------------------------------------------------

def _toy_kernel(x_ref, o_ref):
    o_ref[...] = x_ref[...] * 2.0


def _toy_call(grid, in_spec, out_spec, shape=(256, 256), **kw):
    import jax
    import jax.numpy as jnp
    from jax.experimental import pallas as pl

    if grid is not None:
        kw["grid"] = grid
    f = pl.pallas_call(
        _toy_kernel,
        out_shape=jax.ShapeDtypeStruct(shape, jnp.float32),
        in_specs=[in_spec], out_specs=out_spec,
        interpret=True, **kw)
    return jax.make_jaxpr(f)(jnp.ones(shape, jnp.float32))


def test_palcheck_matrix_and_extras_clean(comm_traced):
    """The production kernels pass: the fused dist chunk's launches (the
    matrix population) and the standalone large-grid builds (where the
    grid actually partitions: pipelined tblock, aliased rb kernel)."""
    assert palcheck.run(traced=comm_traced, extras=False) == []
    extras = palcheck.extra_entries()
    # rb + tblock + quarters (2-D) + tblock 3-D — all four solve-kernel
    # layouts, at grids large enough to partition
    assert len(extras) == 4
    for name, jx in extras:
        vs = palcheck.check_jaxpr(jx.jaxpr, context=f"{name}/")
        assert vs == [], [str(v) for v in vs]
        # the decoded launches carry real kernel anchors
        for launch in palcheck.launches(jx.jaxpr):
            assert "/ops/sor" in launch.path and launch.path.endswith(".py")
            assert launch.line > 0


def test_palcheck_oversized_block_flagged():
    """Mutation: a block whose window exceeds the VMEM budget — the
    failure class `tblock_feasible` guards at build time, now also caught
    on any kernel statically."""
    import jax.numpy as jnp
    from jax.experimental import pallas as pl

    jx = _toy_call(None, pl.BlockSpec((2048, 2048), lambda: (0, 0)),
                   pl.BlockSpec((2048, 2048), lambda: (0, 0)),
                   shape=(2048, 2048))
    vs = palcheck.check_jaxpr(jx.jaxpr, budget=1 << 20)
    assert [v.rule for v in vs] == [palcheck.RULE_VMEM]
    assert "exceeds the budget" in vs[0].message
    # within budget: clean
    assert palcheck.check_jaxpr(jx.jaxpr, budget=64 << 20) == []


def test_palcheck_oob_index_map_flagged():
    """Mutation: an index map shifted one block past the array — every
    grid point's window start must land inside the operand."""
    from jax.experimental import pallas as pl

    jx = _toy_call((2,),
                   pl.BlockSpec((128, 256), lambda i: (i + 1, 0)),
                   pl.BlockSpec((128, 256), lambda i: (i, 0)))
    vs = palcheck.check_jaxpr(jx.jaxpr)
    assert [v.rule for v in vs] == [palcheck.RULE_OOB]
    assert "grid point (1,)" in vs[0].message
    assert "starts at element 256" in vs[0].message


def test_palcheck_mistiled_block_flagged():
    """Mutation: a partitioned block off the (8, 128) f32 granularity is
    flagged per offending dim; a FULL-extent unaligned block is exempt
    (Mosaic pads whole-array windows — the repo's own (40, 128)-style
    blocks rely on that)."""
    from jax.experimental import pallas as pl

    jx = _toy_call((4, 4),
                   pl.BlockSpec((60, 60), lambda i, j: (i, j)),
                   pl.BlockSpec((60, 60), lambda i, j: (i, j)),
                   shape=(240, 240))
    vs = palcheck.check_jaxpr(jx.jaxpr)
    tiles = [v for v in vs if v.rule == palcheck.RULE_TILE]
    assert len(tiles) == 4  # 2 operands x 2 misaligned dims
    assert any("granularity 128" in v.message for v in tiles)
    assert any("granularity 8" in v.message for v in tiles)
    # full-extent block, unaligned sublane: exempt
    jx = _toy_call((1,), pl.BlockSpec((30, 128), lambda i: (0, 0)),
                   pl.BlockSpec((30, 128), lambda i: (0, 0)),
                   shape=(30, 128))
    assert palcheck.check_jaxpr(jx.jaxpr) == []


def test_palcheck_alias_hazards_flagged():
    """Mutations: (a) an aliased pair windowed through DIFFERENT index
    maps — the donated buffer is rewritten elsewhere than it is read;
    (b) a donated input also read through a second operand of the same
    call (use-after-donation)."""
    import jax
    import jax.numpy as jnp
    from jax.experimental import pallas as pl

    def k2(x_ref, y_ref, o_ref):
        o_ref[...] = x_ref[...] + y_ref[...]

    x = jnp.ones((256, 256), jnp.float32)
    f = pl.pallas_call(
        k2, out_shape=jax.ShapeDtypeStruct((256, 256), jnp.float32),
        grid=(2,),
        in_specs=[pl.BlockSpec((128, 256), lambda i: (i, 0)),
                  pl.BlockSpec((128, 256), lambda i: (1 - i, 0))],
        out_specs=pl.BlockSpec((128, 256), lambda i: (i, 0)),
        input_output_aliases={1: 0}, interpret=True)
    vs = palcheck.check_jaxpr(jax.make_jaxpr(f)(x, x).jaxpr)
    assert [v.rule for v in vs] == [palcheck.RULE_ALIAS]
    assert "index maps differ" in vs[0].message

    f2 = pl.pallas_call(
        k2, out_shape=jax.ShapeDtypeStruct((256, 256), jnp.float32),
        input_output_aliases={0: 0}, interpret=True)
    vs = palcheck.check_jaxpr(jax.make_jaxpr(lambda a: f2(a, a))(x).jaxpr)
    assert [v.rule for v in vs] == [palcheck.RULE_ALIAS]
    assert "use-after-donation" in vs[0].message


def test_palcheck_squeezed_block_dims():
    """A pallas_call windowing with squeezed dims (None in the BlockSpec,
    a Mapped sentinel in the jaxpr param) must CHECK, not crash the lint
    driver: extents count as 1 for VMEM/coverage, and squeezed dims are
    exempt from the tiling rule (iteration, not windowing)."""
    import jax
    import jax.numpy as jnp
    from jax.experimental import pallas as pl

    def row_kernel(x_ref, o_ref):
        o_ref[...] = x_ref[...] * 2.0

    f = pl.pallas_call(
        row_kernel,
        out_shape=jax.ShapeDtypeStruct((16, 128), jnp.float32),
        grid=(16,),
        in_specs=[pl.BlockSpec((None, 128), lambda i: (i, 0))],
        out_specs=pl.BlockSpec((None, 128), lambda i: (i, 0)),
        interpret=True)
    jx = jax.make_jaxpr(f)(jnp.ones((16, 128), jnp.float32))
    assert palcheck.check_jaxpr(jx.jaxpr) == []
    (launch,) = palcheck.launches(jx.jaxpr)
    assert palcheck.block_extents(launch.in_mappings[0]) == (1, 128)
    assert palcheck.vmem_estimate(launch) > 0
    # an OOB map through a squeezed dim still flags (start = index * 1)
    f2 = pl.pallas_call(
        row_kernel,
        out_shape=jax.ShapeDtypeStruct((16, 128), jnp.float32),
        grid=(16,),
        in_specs=[pl.BlockSpec((None, 128), lambda i: (i + 1, 0))],
        out_specs=pl.BlockSpec((None, 128), lambda i: (i, 0)),
        interpret=True)
    jx2 = jax.make_jaxpr(f2)(jnp.ones((16, 128), jnp.float32))
    vs = palcheck.check_jaxpr(jx2.jaxpr)
    assert [v.rule for v in vs] == [palcheck.RULE_OOB]


def test_palcheck_vmem_estimate_scratch_and_pipeline():
    """The estimator's two accounting rules on a production kernel: ANY
    operands charge nothing (their windows enter via explicit VMEM
    scratch), and the declared compiler vmem_limit is the default
    budget."""
    name, jx = palcheck.extra_entries()[0]  # rb_iter: ANY + 2 VMEM scratch
    (launch,) = palcheck.launches(jx.jaxpr)
    est = palcheck.vmem_estimate(launch)
    import numpy as np

    want = sum(
        int(np.prod(a.shape)) * np.dtype(a.dtype).itemsize
        for a in launch.scratch_avals if palcheck._mspace(a) == "vmem")
    # + the (1, 1) smem residual block charges nothing; ANY blocks either
    assert est == want > 0
    assert launch.vmem_limit == 100 << 20  # sor_pallas.VMEM_LIMIT_BYTES
    assert launch.aliases == ((0, 0),)
