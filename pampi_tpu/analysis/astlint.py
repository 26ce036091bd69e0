"""AST lint: repo-specific source rules with file:line diagnostics.

Every rule guards a contract that past PRs fixed by hand at least once:

  env-read       environment reads (`os.environ`, `os.getenv`) outside
                 the registered accessor layer (`utils/flags.py`). The
                 accessor records every variable in one inventory, so a
                 rogue read is a knob invisible to the docs, the lint,
                 and the flag-off identity tests.
  raw-shard-map  `jax.shard_map`, or any `shard_map` module import,
                 used outside `parallel/comm.compat_shard_map` — the one
                 call site lives there ONLY (two past PRs routed
                 stragglers).
  np-in-traced   `np.*` inside a traced closure — a def nested in a
                 `_build_*`/`make_*` builder, the repo's convention for
                 the functions jit/while_loop traces per step (builder
                 BODIES run once at build time, where numpy is the
                 correct tool for baking constants): numpy on a tracer
                 fails at trace time, numpy on a constant silently bakes
                 host values/dtypes the precision contract never sees.
  traced-nondet  wall-clock/random calls (`time.*`, `random.*`,
                 `np.random.*`, `datetime.*`) in the same traced
                 contexts — a nondeterministic trace breaks the flag-off
                 byte-identity contract and the XLA cache.
  broad-except   `except Exception:`/bare `except:` without an allow
                 escape — fault classification (models/_driver.py) depends
                 on concrete exception classes reaching it.
  print-call     `print()` in library code where telemetry/progress
                 records exist (CLI entry points are exempt).
  dtype-policy   raw float-dtype literals in solver/ops builder code
                 (`.astype(jnp.float32)`, `jnp.float64(x)`,
                 `dtype=jnp.bfloat16`) — the compute dtype is a POLICY
                 (`utils/precision.resolve_dtype` resolves it once per
                 solver; `precision.cast` declares every intentional
                 downcast), so a hard-coded dtype in models/ or ops/ is
                 a precision decision the preccheck census cannot see
                 coming. Builder-context only (constants baked by
                 builders ARE the traced program); passing a dtype
                 VARIABLE is always fine.

Escape hatch: a trailing `# lint: allow(<rule>[, <rule>...])` comment on
the offending line (for `except` clauses, on the `except` line), with a
short justification after it. The escape is per-line and per-rule — a
file-wide opt-out does not exist by design.

API: `lint_file(path)` / `lint_tree(root)` -> list[Violation]; the
`tools/lint.py` driver renders them as `file:line: [rule] message`.
"""

from __future__ import annotations

import ast
import os
import re
from dataclasses import dataclass

# rule ids (the allow-escape vocabulary)
ENV_READ = "env-read"
RAW_SHARD_MAP = "raw-shard-map"
NP_IN_TRACED = "np-in-traced"
TRACED_NONDET = "traced-nondet"
BROAD_EXCEPT = "broad-except"
PRINT_CALL = "print-call"
DTYPE_POLICY = "dtype-policy"

ALL_RULES = (ENV_READ, RAW_SHARD_MAP, NP_IN_TRACED, TRACED_NONDET,
             BROAD_EXCEPT, PRINT_CALL, DTYPE_POLICY)

# rule sets by tree: library code gets everything; tools/tests are
# harness code (prints, env knobs and numpy are their job) but must still
# route shard_map through the compat shim
LIBRARY_RULES = ALL_RULES
HARNESS_RULES = (RAW_SHARD_MAP,)

# modules where the rule's guarded behaviour IS the module's purpose
ENV_ACCESSOR_FILES = ("utils/flags.py",)
SHARD_MAP_HOME_FILES = ("parallel/comm.py",)
PRINT_EXEMPT_FILES = ("cli.py", "__main__.py", "utils/progress.py",
                      "utils/params.py")

# the dtype-policy rule applies only where solver/ops builders live —
# elsewhere (utils/precision.py above all) a dtype literal IS the policy
DTYPE_POLICY_DIRS = ("models", "ops")

_FLOAT_DTYPE_NAMES = frozenset(
    ("float16", "float32", "float64", "bfloat16",
     "half", "single", "double"))

_ALLOW_RE = re.compile(r"#\s*lint:\s*allow\(([^)]*)\)")


@dataclass
class Violation:
    path: str
    line: int
    rule: str
    message: str

    def __str__(self) -> str:
        return f"{self.path}:{self.line}: [{self.rule}] {self.message}"


def _allowed(source_lines: list[str], lineno: int, rule: str) -> bool:
    """True when the 1-indexed line carries `# lint: allow(...)` naming
    `rule` (comma-separated list accepted)."""
    if not (1 <= lineno <= len(source_lines)):
        return False
    m = _ALLOW_RE.search(source_lines[lineno - 1])
    if not m:
        return False
    allowed = {tok.strip() for tok in m.group(1).split(",")}
    return rule in allowed


def _dotted(node: ast.AST) -> str:
    """`a.b.c` attribute chains as a dotted string ('' when not one)."""
    parts: list[str] = []
    while isinstance(node, ast.Attribute):
        parts.append(node.attr)
        node = node.value
    if isinstance(node, ast.Name):
        parts.append(node.id)
        return ".".join(reversed(parts))
    return ""


def _dtype_literal(node: ast.AST) -> str:
    """The spelled-out float-dtype literal an expression hard-codes
    ('jnp.float32', "'float64'"), or '' when the expression is a name/
    computed value (a dtype VARIABLE — policy-resolved, always fine)."""
    if isinstance(node, ast.Constant) and isinstance(node.value, str) \
            and node.value in _FLOAT_DTYPE_NAMES:
        return repr(node.value)
    dotted = _dotted(node)
    if dotted:
        parts = dotted.split(".")
        if parts[-1] in _FLOAT_DTYPE_NAMES \
                and parts[0] in ("jnp", "np", "numpy", "jax"):
            return dotted
    return ""


class _Linter(ast.NodeVisitor):
    def __init__(self, path: str, rel: str, source: str, rules):
        self.path = path
        self.rel = rel
        self.lines = source.splitlines()
        self.rules = set(rules)
        self.out: list[Violation] = []
        # stack of (function name, is_traced_context)
        self._funcs: list[tuple[str, bool]] = []
        # local aliases of a `shard_map` MODULE
        # (`import jax._src.shard_map as sm` -> "sm")
        self._sm_aliases: set[str] = set()

    # -- helpers --------------------------------------------------------
    def _emit(self, node: ast.AST, rule: str, message: str) -> None:
        if rule not in self.rules:
            return
        if _allowed(self.lines, node.lineno, rule):
            return
        self.out.append(Violation(self.rel, node.lineno, rule, message))

    def _traced(self) -> bool:
        """Inside a def nested under a `_build_*`/`make_*` builder (the
        repo's traced-closure convention)."""
        return any(traced for _name, traced in self._funcs)

    def _in_builder(self) -> bool:
        """Inside a builder's own body OR a def nested under one — the
        dtype-policy scope: both the baked constants and the traced
        closures are the program the precision contract governs."""
        return self._traced() or any(
            name.startswith(("_build_", "make_"))
            for name, _traced in self._funcs)

    # -- visitors -------------------------------------------------------
    def _visit_funcdef(self, node) -> None:
        name = node.name
        parent_is_builder = bool(self._funcs) and (
            self._funcs[-1][0].startswith("_build_")
            or self._funcs[-1][0].startswith("make_")
        )
        traced = parent_is_builder or (self._funcs and self._funcs[-1][1])
        self._funcs.append((name, bool(traced)))
        self.generic_visit(node)
        self._funcs.pop()

    visit_FunctionDef = _visit_funcdef
    visit_AsyncFunctionDef = _visit_funcdef

    def visit_Call(self, node: ast.Call) -> None:
        dotted = _dotted(node.func)
        if dotted in ("os.environ.get", "os.getenv", "os.environ.setdefault"):
            self._emit(node, ENV_READ,
                       f"{dotted} outside utils/flags.py — route through "
                       "flags.env()/set_default() so the env-var inventory "
                       "stays complete")
        parts = dotted.split(".") if dotted else []
        raw_sm = parts and parts[-1] == "shard_map" and (
            dotted == "shard_map"                    # from jax import ...
            or parts[0] == "jax"                     # jax.shard_map & co
            or parts[0] in self._sm_aliases          # aliased module
        )
        if raw_sm:
            # the call site is the authoritative trigger (the import-site
            # rules can't see `from jax import shard_map` on every jax
            # version); method calls on repo objects (CartComm.shard_map
            # routes through the shim internally) don't match — their
            # receiver is neither jax nor a tracked module alias
            self._emit(node, RAW_SHARD_MAP,
                       f"{dotted}() called directly — route through "
                       "parallel/comm.compat_shard_map (the one "
                       "version shim)")
        if isinstance(node.func, ast.Name) and node.func.id == "print":
            self._emit(node, PRINT_CALL,
                       "print() in library code — emit a telemetry record "
                       "(utils/telemetry), a progress update, or a warning "
                       "instead")
        if self._traced():
            root = dotted.split(".")[0] if dotted else ""
            if root == "np" and not dotted.startswith("np.random"):
                self._emit(node, NP_IN_TRACED,
                           f"{dotted}() inside a traced context — numpy "
                           "bakes host values/dtypes into the trace; use "
                           "jnp (or hoist to the builder body and mark "
                           "the constant intent)")
            if (dotted.startswith("np.random") or root in ("random",)
                    or dotted.startswith("datetime.")
                    or dotted in ("time.time", "time.perf_counter",
                                  "time.monotonic")):
                self._emit(node, TRACED_NONDET,
                           f"{dotted}() inside a traced context — a "
                           "nondeterministic trace breaks the flag-off "
                           "byte-identity contract and the XLA cache")
        if self._in_builder():
            # raw `.astype(<float literal>)`
            if isinstance(node.func, ast.Attribute) \
                    and node.func.attr == "astype" and node.args:
                lit = _dtype_literal(node.args[0])
                if lit:
                    self._emit(node, DTYPE_POLICY,
                               f".astype({lit}) hard-codes a float dtype "
                               "in builder code — the compute dtype is "
                               "policy (utils/precision.resolve_dtype); "
                               "declare an intentional downcast through "
                               "precision.cast(x, dtype, why)")
            # `jnp.float64(x)` constructor casts
            parts = dotted.split(".") if dotted else []
            if len(parts) == 2 and parts[0] in ("jnp", "np", "numpy") \
                    and parts[1] in _FLOAT_DTYPE_NAMES and node.args:
                self._emit(node, DTYPE_POLICY,
                           f"{dotted}(...) hard-codes a float dtype in "
                           "builder code — resolve the dtype through "
                           "utils/precision instead of constructing one")
            # `dtype=<float literal>` keywords
            for kw in node.keywords:
                if kw.arg == "dtype":
                    lit = _dtype_literal(kw.value)
                    if lit:
                        self._emit(node, DTYPE_POLICY,
                                   f"dtype={lit} hard-codes a float dtype "
                                   "in builder code — thread the solver's "
                                   "policy dtype (or annotate `# lint: "
                                   "allow(dtype-policy)` with the why)")
        self.generic_visit(node)

    def visit_Subscript(self, node: ast.Subscript) -> None:
        if _dotted(node.value) == "os.environ":
            self._emit(node, ENV_READ,
                       "os.environ[...] outside utils/flags.py — route "
                       "through flags.env() so the env-var inventory "
                       "stays complete")
        self.generic_visit(node)

    def visit_ImportFrom(self, node: ast.ImportFrom) -> None:
        mod = node.module or ""
        if mod.split(".")[-1] == "shard_map" or (
            mod in ("jax", "jax.experimental")
            and any(a.name == "shard_map" for a in node.names)
        ):
            self._emit(node, RAW_SHARD_MAP,
                       f"importing shard_map from {mod} — use "
                       "parallel/comm.compat_shard_map (the one call "
                       "site)")
        self.generic_visit(node)

    def visit_Import(self, node: ast.Import) -> None:
        for a in node.names:
            if a.name.split(".")[-1] == "shard_map":
                if a.asname:
                    self._sm_aliases.add(a.asname)
                self._emit(node, RAW_SHARD_MAP,
                           f"importing {a.name} — use parallel/comm."
                           "compat_shard_map (the one call site)")
        self.generic_visit(node)

    def visit_ExceptHandler(self, node: ast.ExceptHandler) -> None:
        broad = node.type is None or (
            isinstance(node.type, ast.Name) and node.type.id == "Exception"
        )
        if broad:
            self._emit(node, BROAD_EXCEPT,
                       "bare `except Exception` — narrow to the concrete "
                       "class(es), or annotate `# lint: allow(broad-"
                       "except)` with a one-line justification")
        self.generic_visit(node)


def _rel(path: str, root: str | None) -> str:
    if root:
        try:
            return os.path.relpath(path, root)
        except ValueError:
            pass
    return path


def rules_for(rel: str):
    """Rule set by tree position (see module docstring)."""
    top = rel.replace(os.sep, "/").split("/", 1)[0]
    if top in ("tools", "tests", "scripts"):
        return HARNESS_RULES
    return LIBRARY_RULES


def lint_file(path: str, rules=None, root: str | None = None):
    """Lint one file. `rules=None` selects by tree position. Returns
    (violations, None) or ([], error_string) on a parse failure."""
    rel = _rel(path, root)
    rules = rules_for(rel) if rules is None else rules
    norm = rel.replace(os.sep, "/")
    rules = set(rules)

    def matches(f: str) -> bool:
        # path-component boundary, never a bare suffix: `webcli.py` must
        # not inherit `cli.py`'s exemption
        return norm == f or norm.endswith("/" + f)

    # module-purpose exemptions (the rule's target behaviour IS the file)
    if any(matches(f) for f in ENV_ACCESSOR_FILES):
        rules.discard(ENV_READ)
    if any(matches(f) for f in SHARD_MAP_HOME_FILES):
        rules.discard(RAW_SHARD_MAP)
    if any(matches(f) for f in PRINT_EXEMPT_FILES):
        rules.discard(PRINT_CALL)
    # dtype-policy scopes to the solver/ops trees by directory component
    comps = norm.split("/")[:-1]
    if not any(d in comps for d in DTYPE_POLICY_DIRS):
        rules.discard(DTYPE_POLICY)
    try:
        with open(path, encoding="utf-8") as fh:
            source = fh.read()
        tree = ast.parse(source, filename=path)
    except (OSError, SyntaxError) as exc:
        return [], f"{rel}: unparseable ({exc})"
    linter = _Linter(path, rel, source, rules)
    linter.visit(tree)
    return sorted(linter.out, key=lambda v: (v.path, v.line)), None


def lint_tree(root: str, subdirs=("pampi_tpu", "tools", "tests")):
    """Lint every .py under root/<subdirs>. Returns (violations, errors)."""
    violations: list[Violation] = []
    errors: list[str] = []
    for sub in subdirs:
        base = os.path.join(root, sub)
        for dirpath, dirnames, filenames in os.walk(base):
            dirnames[:] = [d for d in dirnames if d != "__pycache__"]
            for fn in sorted(filenames):
                if not fn.endswith(".py"):
                    continue
                vs, err = lint_file(os.path.join(dirpath, fn), root=root)
                violations += vs
                if err:
                    errors.append(err)
    return violations, errors


def env_inventory(root: str) -> dict[str, list[str]]:
    """The static env-var inventory: every string literal read through
    `flags.env(...)` / `flags._on(...)` / `flags.set_default(...)` in the
    library tree, mapped to its `file:line` registration sites. The
    env-read rule makes this complete by construction."""
    inv: dict[str, list[str]] = {}
    base = os.path.join(root, "pampi_tpu")
    for dirpath, dirnames, filenames in os.walk(base):
        dirnames[:] = [d for d in dirnames if d != "__pycache__"]
        for fn in sorted(filenames):
            if not fn.endswith(".py"):
                continue
            path = os.path.join(dirpath, fn)
            try:
                tree = ast.parse(open(path, encoding="utf-8").read(),
                                 filename=path)
            except (OSError, SyntaxError):
                continue
            rel = _rel(path, root)
            for node in ast.walk(tree):
                if not isinstance(node, ast.Call):
                    continue
                name = _dotted(node.func)
                if not (name.endswith(".env") or name.endswith(".set_default")
                        or name.endswith("._on") or name in (
                            "env", "set_default", "_on")):
                    continue
                if node.args and isinstance(node.args[0], ast.Constant) \
                        and isinstance(node.args[0].value, str):
                    var = node.args[0].value
                    if var.startswith(("PAMPI_", "JAX_")):
                        inv.setdefault(var, []).append(
                            f"{rel}:{node.lineno}")
    return inv
