"""Precision-flow contract checker: where does every bit of precision
go, statically, before the mixed-precision knob exists?

ROADMAP item 2's mixed-precision bullet (bf16/f32 smoothing under an f64
residual) needs a merge gate: today dtype policy is a runtime convention
(`utils/precision.py`), the f32 eps-floor caveat is a build-time warning,
and the fused-vs-ladder summation-order hazard was found by hand. This
pass derives the precision contract from the SAME one trace of the config
matrix the jaxpr/comm/pallas passes share (`jaxprcheck.trace_matrix`),
pins it env-keyed in the `precision` section of CONTRACTS.json, and fails
drift with per-site src->dst diffs + file:line via jaxpr source info.

Four analyses over every config's chunk jaxpr:

  dtype lattice   every `convert_element_type` is censused by
                  (src->dst dtype, scope) and classified narrowing /
                  widening / preserving. A NARROWING float cast must be
                  DECLARED by routing through `utils/precision.cast(x,
                  dtype, why)` — the `precision.cast.<why>` named scope
                  is read off the eqn's name stack exactly like the comm
                  census reads `halo_exchange.*`. An undeclared downcast
                  fails with its file:line (prec-cast).
  oracle purity   configs marked `oracle=True` (the jnp f64 parity
                  oracles) must contain ZERO sub-f64 float compute
                  anywhere in the trace — the property the mixed-
                  precision knob must never break (prec-oracle).
                  Detection uses jnp.issubdtype: the ml_dtypes extension
                  floats (bfloat16) are invisible to np.floating.
  reduction order each `reduce_sum`/cumulative reduction whose result
                  feeds a while-loop convergence predicate (the residual
                  accumulations behind the eps-floor caveat) must be
                  f64-accumulated or declared in
                  `precision.DECLARED_ORDER_SENSITIVE` (prec-reduce).
                  The audit also generalizes `check_eps_floor` from a
                  build-time warning into a matrix-wide static check of
                  every (eps, ncells, dtype) triple the standard configs
                  imply (prec-floor).
  advisory bf16   configs marked `advisory=True` (the forced-bf16
                  scouts) run every analysis and PIN their census in the
                  baseline, but their rule findings are REPORTED (the
                  driver prints them) instead of gating — the pass
                  prices exactly which casts/accumulations the future
                  `tpu_dtype bf16` lanes add before that knob lands.
                  Census drift still gates: the scout's precision shape
                  is a contract like any other.

Baseline workflow: `tools/lint.py --only prec` checks against the
`precision` section; `--update` regenerates it through the same merged
single-write as the configs/comm sections (prec-baseline on drift).
"""

from __future__ import annotations

import os

from .astlint import Violation
from .jaxprcheck import _anchor, float_dtypes, iter_eqns

RULE_CAST = "prec-cast"
RULE_ORACLE = "prec-oracle"
RULE_REDUCE = "prec-reduce"
RULE_FLOOR = "prec-floor"
RULE_BASELINE = "prec-baseline"

# the declared-downcast scope convention (utils/precision.cast)
CAST_SCOPE_PREFIX = "precision.cast."

# order-sensitive accumulation primitives: sequential/tree association
# changes their result; max/min-style reductions are order-insensitive
REDUCTIONS = ("reduce_sum", "cumsum", "cumlogsumexp")
COMPARISONS = ("lt", "le", "gt", "ge", "eq", "ne")


# ---------------------------------------------------------------------------
# jaxpr helpers
# ---------------------------------------------------------------------------

def _is_literal(v) -> bool:
    return type(v).__name__ == "Literal"


def _sub_jaxprs(eqn):
    for v in eqn.params.values():
        vals = v if isinstance(v, (tuple, list)) else (v,)
        for x in vals:
            if type(x).__name__ == "ClosedJaxpr":
                yield x.jaxpr
            elif type(x).__name__ == "Jaxpr":
                yield x


def _dtype_of(v):
    return getattr(getattr(v, "aval", None), "dtype", None)


def _float_name(dt) -> str | None:
    """str dtype name when `dt` is ANY float (incl. the ml_dtypes
    extension floats np.issubdtype cannot see), else None."""
    import jax.numpy as jnp

    if dt is None:
        return None
    try:
        if jnp.issubdtype(dt, jnp.floating):
            return str(jnp.dtype(dt))
    except TypeError:
        return None
    return None


def float_bits(name) -> int:
    import jax.numpy as jnp

    return int(jnp.finfo(name).bits)


def eqn_src(eqn) -> tuple[str, int]:
    """(file, line) of the user frame that created an eqn — the
    diagnostic anchor of every per-site finding."""
    try:
        from jax._src import source_info_util

        fr = source_info_util.user_frame(eqn.source_info.traceback)
    except (ImportError, AttributeError):
        fr = None
    if fr is None:
        return "<unknown>", 0
    return fr.file_name, int(fr.start_line)


def cast_scope(eqn) -> str:
    """The `precision.cast.<why>` token on an eqn's name stack ('' when
    undeclared) — same name-stack read as commcheck.scoped_exchanges."""
    stack = str(getattr(eqn.source_info, "name_stack", "") or "")
    for part in stack.split("/"):
        if part.startswith(CAST_SCOPE_PREFIX):
            return part[len(CAST_SCOPE_PREFIX):]
    return ""


# ---------------------------------------------------------------------------
# (1) dtype-lattice dataflow: the cast census
# ---------------------------------------------------------------------------

def cast_sites(jaxpr) -> list[dict]:
    """Every `convert_element_type` anywhere in the program, as a site
    dict: src/dst dtype names, narrowing/widening/preserving/boundary
    classification (float lattice; int<->float edges are 'boundary'),
    declared scope, file:line."""
    import jax.numpy as jnp

    sites = []
    for e in iter_eqns(jaxpr):
        if e.primitive.name != "convert_element_type":
            continue
        src_dt = _dtype_of(e.invars[0]) if e.invars else None
        dst_dt = _dtype_of(e.outvars[0]) if e.outvars else None
        if src_dt is None or dst_dt is None:
            continue
        src_f, dst_f = _float_name(src_dt), _float_name(dst_dt)
        if src_f and dst_f:
            sb, db = float_bits(src_f), float_bits(dst_f)
            kind = ("narrowing" if db < sb
                    else "widening" if db > sb else "preserving")
        else:
            kind = "boundary"
        f, ln = eqn_src(e)
        sites.append({
            "src": str(jnp.dtype(src_dt)), "dst": str(jnp.dtype(dst_dt)),
            "kind": kind, "scope": cast_scope(e), "file": f, "line": ln,
        })
    return sites


def site_key(site: dict) -> str:
    """Census key of one cast site: 'float64->bfloat16@implicit' /
    '...@metrics' (the declared `why`)."""
    return (f"{site['src']}->{site['dst']}"
            f"@{site['scope'] or 'implicit'}")


def cast_census(sites: list[dict]) -> dict[str, int]:
    out: dict[str, int] = {}
    for s in sites:
        k = site_key(s)
        out[k] = out.get(k, 0) + 1
    return out


def implicit_narrowing(sites: list[dict]) -> list[dict]:
    """The banned class: float downcasts carrying no declared scope."""
    return [s for s in sites
            if s["kind"] == "narrowing" and not s["scope"]]


# ---------------------------------------------------------------------------
# (2) oracle purity
# ---------------------------------------------------------------------------

def subf64_sites(jaxpr) -> list[dict]:
    """Eqns producing any sub-f64 float output — empty on a pure f64
    oracle program."""
    out = []
    for e in iter_eqns(jaxpr):
        for v in e.outvars:
            nm = _float_name(_dtype_of(v))
            if nm and float_bits(nm) < 64:
                f, ln = eqn_src(e)
                out.append({"prim": e.primitive.name, "dtype": nm,
                            "file": f, "line": ln})
                break
    return out


# ---------------------------------------------------------------------------
# (3) reduction-order audit
# ---------------------------------------------------------------------------

def _reduction_site(e) -> dict | None:
    if e.primitive.name not in REDUCTIONS:
        return None
    nm = _float_name(_dtype_of(e.outvars[0])) if e.outvars else None
    if nm is None:
        return None
    f, ln = eqn_src(e)
    return {"prim": e.primitive.name, "dtype": nm, "file": f, "line": ln}


def _cond_read_carry(cond_closed, nconsts: int) -> set[int]:
    """Carry positions a while cond's float comparisons transitively
    read (backward slice over the cond jaxpr's top-level eqns)."""
    cj = cond_closed.jaxpr
    prod = {}
    for e in cj.eqns:
        for ov in e.outvars:
            prod[id(ov)] = e
    work = [e for e in cj.eqns
            if e.primitive.name in COMPARISONS
            and any(_float_name(_dtype_of(v))
                    for v in e.invars if not _is_literal(v))]
    reach: set[int] = set()
    seen: set[int] = set()
    while work:
        e = work.pop()
        if id(e) in seen:
            continue
        seen.add(id(e))
        for v in e.invars:
            if _is_literal(v):
                continue
            reach.add(id(v))
            pe = prod.get(id(v))
            if pe is not None:
                work.append(pe)
    return {i - nconsts for i, v in enumerate(cj.invars)
            if id(v) in reach and i >= nconsts}


def _dedup(sites: list[dict]) -> list[dict]:
    uniq = {(s["file"], s["line"], s["prim"], s["dtype"]): s
            for s in sites}
    return list(uniq.values())


def _body_reduction_taint(body_closed) -> dict[int, list[dict]]:
    """Forward taint over the while body's top-level eqns: which carry
    outvar positions a float reduction's result reaches. Reductions
    inside an eqn's sub-jaxprs (pjit bodies, pallas kernels, nested
    loops) taint that eqn's outputs — conservative across control flow;
    nested whiles additionally get their own direct audit."""
    bj = body_closed.jaxpr
    by_var: dict[int, list[dict]] = {}
    for e in bj.eqns:
        sites: list[dict] = []
        for v in e.invars:
            if not _is_literal(v):
                sites += by_var.get(id(v), [])
        own = _reduction_site(e)
        if own is not None:
            sites = sites + [own]
        else:
            for sub in _sub_jaxprs(e):
                for se in iter_eqns(sub):
                    s = _reduction_site(se)
                    if s is not None:
                        sites.append(s)
        if sites:
            sites = _dedup(sites)
            for v in e.outvars:
                by_var[id(v)] = sites
    return {pos: by_var[id(v)] for pos, v in enumerate(bj.outvars)
            if id(v) in by_var}


def convergence_reductions(jaxpr) -> list[dict]:
    """Every float reduction whose result feeds a while convergence
    predicate, anywhere in the program (each while — including nested
    solve loops — is audited against its own cond)."""
    out: list[dict] = []
    for e in iter_eqns(jaxpr):
        if e.primitive.name != "while":
            continue
        cond_c = e.params.get("cond_jaxpr")
        body_c = e.params.get("body_jaxpr")
        if cond_c is None or body_c is None:
            continue
        read = _cond_read_carry(cond_c, e.params.get("cond_nconsts", 0))
        if not read:
            continue
        taint = _body_reduction_taint(body_c)
        nbc = e.params.get("body_nconsts", 0)
        del nbc  # body outvars ARE the carry; consts only pad invars
        for pos, sites in taint.items():
            if pos in read:
                out += sites
    return _dedup(out)


def registry_key(site: dict) -> str:
    """DECLARED_ORDER_SENSITIVE key of one reduction site:
    '<file basename>:<accumulator dtype>' — names the trade, survives
    line churn."""
    return f"{os.path.basename(site['file'])}:{site['dtype']}"


# ---------------------------------------------------------------------------
# the per-config entry + checks
# ---------------------------------------------------------------------------

def config_entry(traced) -> tuple[dict, list[dict], list[dict]]:
    """(fresh `precision` baseline entry, cast sites, convergence
    reduction sites) for one traced config."""
    import jax.numpy as jnp

    sites = cast_sites(traced.jaxpr.jaxpr)
    reds = convergence_reductions(traced.jaxpr.jaxpr)
    red_census: dict[str, int] = {}
    for s in reds:
        k = registry_key(s)
        red_census[k] = red_census.get(k, 0) + 1
    entry = {
        "dtype": str(jnp.dtype(traced.solver.dtype)),
        "float_dtypes": sorted(float_dtypes(traced.jaxpr.jaxpr)),
        "casts": cast_census(sites),
        "narrowing": sum(1 for s in sites if s["kind"] == "narrowing"),
        "reductions": red_census,
    }
    if traced.cfg.oracle:
        entry["oracle"] = True
    if traced.cfg.advisory:
        entry["advisory"] = True
    return entry, sites, reds


def _diff_casts(old: dict, new: dict, sites: list[dict]) -> list[str]:
    """Per-site src->dst census diff, with the fresh sites' file:line
    so a drifted key points at the code that moved."""
    lines = []
    for key in sorted(set(old) | set(new)):
        a, b = old.get(key, 0), new.get(key, 0)
        if a == b:
            continue
        where = sorted({f"{s['file']}:{s['line']}"
                        for s in sites if site_key(s) == key})[:3]
        lines.append(f"{key}: {a} -> {b} ({b - a:+d})"
                     + (f" at {'; '.join(where)}" if where else ""))
    return lines


def check_config(traced, baseline: dict | None,
                 env_matches: bool) -> tuple[list[Violation], dict, list]:
    """One traced config against the four precision rules and its
    `precision` baseline entry. Returns (violations, fresh entry,
    advisory notes) — on an `advisory` config the rule findings land in
    the notes (the driver reports them) and only baseline drift gates."""
    from ..utils import precision

    cfg = traced.cfg
    path, line = _anchor(cfg.family)
    entry, sites, reds = config_entry(traced)
    findings: list[tuple[str, str]] = []

    # (1) implicit-narrowing ban
    for s in implicit_narrowing(sites):
        findings.append((RULE_CAST,
                         f"implicit downcast {s['src']} -> {s['dst']} at "
                         f"{s['file']}:{s['line']} — declare it through "
                         "utils/precision.cast(x, dtype, why) so the "
                         "census carries its purpose"))
    # (2) oracle purity
    if cfg.oracle:
        bad = subf64_sites(traced.jaxpr.jaxpr)
        for s in bad[:3]:
            findings.append((RULE_ORACLE,
                             f"f64 parity oracle computes at {s['dtype']} "
                             f"({s['prim']} at {s['file']}:{s['line']}) — "
                             "the oracle must stay pure f64 end-to-end"))
        if len(bad) > 3:
            findings.append((RULE_ORACLE,
                             f"... and {len(bad) - 3} more sub-f64 "
                             "site(s)"))
    # (3) reduction-order audit
    for s in reds:
        if float_bits(s["dtype"]) >= 64:
            continue
        key = registry_key(s)
        if key not in precision.DECLARED_ORDER_SENSITIVE:
            findings.append((RULE_REDUCE,
                             f"{s['prim']} accumulates at {s['dtype']} "
                             "and feeds a convergence predicate "
                             f"({s['file']}:{s['line']}) — accumulate at "
                             f"f64 or declare {key!r} in "
                             "precision.DECLARED_ORDER_SENSITIVE with a "
                             "why"))
    # (4) the static eps-floor check, matrix-wide: every (eps, ncells,
    # dtype) triple the config implies, without building a solve
    p = cfg.params
    eps = float(p.get("eps", 0.0) or 0.0)
    ncells = int(p.get("imax", 1)) * int(p.get("jmax", 1)) \
        * int(p.get("kmax", 1) or 1)
    floor = precision.residual_floor(ncells, traced.solver.dtype)
    if 0.0 < eps < 10.0 * floor:
        findings.append((RULE_FLOOR,
                         f"eps={eps:g} sits within a decade of the "
                         f"{entry['dtype']} residual floor (~{floor:.3g} "
                         f"at {ncells} cells) — convergence there "
                         "measures summation-order noise (raise eps or "
                         "run fixed-iteration, eps=0)"))

    vs: list[Violation] = []
    notes: list[str] = []
    if cfg.advisory:
        notes = [f"{cfg.name}: [{r}] {m}" for r, m in findings]
    else:
        vs = [Violation(path, line, r, f"{cfg.name}: {m}")
              for r, m in findings]

    # baseline comparison — env-gated like every trace pass; advisory
    # configs gate here too (the scout's census is pinned, its rule
    # findings are not)
    if baseline is not None and env_matches:
        def emit(msg):
            vs.append(Violation(path, line, RULE_BASELINE,
                                f"{cfg.name}: {msg}"))

        if baseline.get("dtype") != entry["dtype"]:
            emit(f"compute dtype drifted from the precision baseline: "
                 f"{baseline.get('dtype')} -> {entry['dtype']} "
                 "(tools/lint.py --update if intended)")
        if baseline.get("float_dtypes") != entry["float_dtypes"]:
            emit(f"float dtype set drifted: "
                 f"{baseline.get('float_dtypes')} -> "
                 f"{entry['float_dtypes']} (tools/lint.py --update if "
                 "intended)")
        if baseline.get("casts") != entry["casts"]:
            diff = _diff_casts(baseline.get("casts", {}),
                               entry["casts"], sites)
            emit("cast census drifted from the precision baseline: "
                 + "; ".join(diff)
                 + " (tools/lint.py --update if intended)")
        if baseline.get("reductions") != entry["reductions"]:
            old_r = baseline.get("reductions", {})
            rdiff = [f"{k}: {old_r.get(k, 0)} -> "
                     f"{entry['reductions'].get(k, 0)}"
                     for k in sorted(set(old_r) | set(entry["reductions"]))
                     if old_r.get(k, 0) != entry["reductions"].get(k, 0)]
            emit("convergence-reduction census drifted: "
                 + "; ".join(rdiff)
                 + " (tools/lint.py --update if intended)")
    return vs, entry, notes


def run(baseline: dict | None = None, configs=None, update: bool = False,
        traced=None, env_matches: bool = True) -> tuple[list, dict, list]:
    """Check every config of the matrix. `baseline` is the `precision`
    section of CONTRACTS.json ({config name: entry}); returns
    (violations, fresh precision section, advisory notes). `traced`
    (jaxprcheck.trace_matrix) shares solver builds across passes."""
    from . import jaxprcheck

    if traced is None:
        traced = jaxprcheck.trace_matrix(configs)
    vs: list[Violation] = []
    fresh: dict[str, dict] = {}
    notes: list[str] = []
    for t in traced:
        entry = (baseline or {}).get(t.cfg.name)
        if entry is None and baseline is not None and not update:
            vs.append(Violation(
                "CONTRACTS.json", 1, RULE_BASELINE,
                f"{t.cfg.name}: no precision baseline entry "
                "(tools/lint.py --update)"))
        t_vs, fresh_entry, t_notes = check_config(
            t, None if update else entry, env_matches)
        vs += t_vs
        notes += t_notes
        fresh[t.cfg.name] = fresh_entry
    return vs, fresh, notes
