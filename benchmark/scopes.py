"""Per-layer numbers from the program's own spans and scopes.

The program stamps its layers into what a profiler trace holds:

  device scopes   every op of the pressure solve carries `solve` in its
                  op_name metadata, every op of the rest of the step
                  `phases` (jax.named_scope; utils/profiling.scoped)
  kernel names    every Pallas kernel is an HLO instruction named after
                  its `name=` (sor2d_rb_quarters.1, ns2d_pre.3, ...)
  host spans      the drive loop's drive.dispatch, drive.wait and
                  drive.sync around each chunk (utils/profiling.region)

The trace's device op events carry no op_name, so an op's scope comes
from the compiled program's HLO text, keyed by module and instruction
name (`op_scopes`). `reduce` then measures, per device and inside the
harness's replay spans (the trace as benchmark/tracered.load keeps it):

  scope_ns             per scope, the union of the intervals of its ops
  solve_launches       executions of Pallas kernels under `solve`
  host_bound_idle_ns   device idle while the host was not in drive.wait:
                       idle the host caused (dispatching, bookkeeping,
                       work outside the loop), not the device's own gaps

`idle_gaps` names the longest device gaps after the innermost program
span over their midpoint, and by tracered's rule where none covers it.
The readers at the end turn a reduction into the four per-layer numbers.
"""

from __future__ import annotations

import re

import tracered

SCOPES = ("solve", "phases")
DRIVE_SPANS = ("drive.dispatch", "drive.wait", "drive.sync")
# host spans the program writes (utils/profiling.region): the drive loop's
# and the CLI's regions
PROGRAM_SPANS = DRIVE_SPANS + ("solve", "timeloop", "writeResult")
WAIT = "drive.wait"
KERNEL_RE = re.compile(r'custom_call_target="tpu_custom_call"')
MODULE_RE = re.compile(r"^HloModule ([\w.\-]+)", re.M)
NAME_RE = re.compile(r"^\s*(?:ROOT\s+)?%?([\w.\-]+) = ", re.M)
INSTR_RE = re.compile(
    r'^\s*(?:ROOT\s+)?%?([\w.\-]+) = .*?metadata=\{[^}]*op_name="([^"]*)"',
    re.M)


def scope_of(op_name: str) -> str | None:
    """The layer scope an op_name path names (its innermost of SCOPES)."""
    parts = op_name.split("/")
    for part in reversed(parts):
        if part in SCOPES:
            return part
    return None


def op_scopes(hlo_text: str) -> dict:
    """{"module": <HloModule name>, "ops": {instruction: scope},
    "names": [every instruction]} for the compiled program's HLO text;
    "ops" leaves out the instructions under no scope."""
    m = MODULE_RE.search(hlo_text)
    ops = {}
    for name, op_name in INSTR_RE.findall(hlo_text):
        scope = scope_of(op_name)
        if scope is not None:
            ops[name] = scope
    return {"module": m.group(1) if m else "", "ops": ops,
            "names": sorted(set(NAME_RE.findall(hlo_text)))}


def module_base(name: str) -> str:
    """A trace's module name without its program id."""
    return name.split("(", 1)[0]


def reduce(trace: dict, scopes: dict) -> dict:
    """The scope reduction of a trace (tracered.load's form), given the
    chunk program's `op_scopes`."""
    replay = tracered.merge([[s, s + d] for n, s, d in trace["host"]
                             if n == tracered.REPLAY])
    wait = tracered.merge([[s, s + d] for n, s, d in trace["host"]
                           if n == WAIT])
    wait_in = tracered.clip(wait, replay)
    module = scopes.get("module", "")
    table = scopes.get("ops", {})
    per_dev = []
    for dev in trace["devices"]:
        leaf = [(n, s, s + d, lab) for n, s, d, lab in dev["ops"]
                if d > 0 and not tracered.CONTAINER_RE.search(
                    lab.split(" = ", 1)[-1])]
        inside = tracered.clip(tracered.merge([[s, e] for _, s, e, _ in leaf]),
                               replay)
        if not inside:
            continue
        execs = sorted((s, s + d) for n, s, d in dev["modules"]
                       if module_base(n) == module)
        by_scope = {k: [] for k in SCOPES}
        launches = 0
        j = 0
        for n, s, e, lab in sorted(leaf, key=lambda x: x[1]):
            while j < len(execs) and execs[j][1] < s:
                j += 1
            if j >= len(execs) or execs[j][0] > s:
                continue  # not an op of the chunk program
            scope = table.get(n)
            if scope is None or not tracered.clip([[s, e]], replay):
                continue
            by_scope[scope].append([s, e])
            if scope == "solve" and KERNEL_RE.search(lab):
                launches += 1
        idle = _complement(inside, replay)
        per_dev.append({
            "name": dev["name"],
            "scope_ns": {k: tracered.length(tracered.clip(
                tracered.merge(v), replay)) for k, v in by_scope.items()},
            "solve_launches": launches,
            "host_bound_idle_ns": tracered.exposed(idle, wait_in),
        })
    return {"window_ns": tracered.length(replay), "devices": per_dev,
            "drive_spans": bool(wait)}


def _complement(busy, spans):
    """The parts of the merged `spans` that the merged `busy` leaves
    free."""
    out = []
    for a, b in spans:
        pos = a
        for s, e in busy:
            if e <= pos or s >= b:
                continue
            if s > pos:
                out.append([pos, s])
            pos = max(pos, e)
        if pos < b:
            out.append([pos, b])
    return out


def idle_gaps(trace: dict, top: int = 10) -> list:
    """tracered's `idle_gaps` of the same trace ([name, seconds], longest
    first), each named after the innermost program span over its midpoint
    where one covers it, by tracered's rule (the innermost host event)
    otherwise."""
    spans = tracered.merge([[s, s + d] for n, s, d in trace["host"]
                            if n == tracered.REPLAY])
    host = [(n, s, s + d) for n, s, d in trace["host"]
            if n != tracered.REPLAY]
    gaps = []
    for dev in trace["devices"]:
        leaf = [[s, s + d] for n, s, d, lab in dev["ops"]
                if d > 0 and not tracered.CONTAINER_RE.search(
                    lab.split(" = ", 1)[-1])]
        inside = tracered.clip(tracered.merge(leaf), spans)
        for i in range(len(inside) - 1):
            s, e = inside[i][1], inside[i + 1][0]
            if any(a <= s and e <= b for a, b in spans):
                gaps.append((e - s, s, e))
    out = []
    for g, s, e in sorted(gaps, reverse=True)[:top]:
        mid = 0.5 * (s + e)
        over = [(b - a, n) for n, a, b in host if a <= mid <= b]
        mine = [x for x in over if x[1] in PROGRAM_SPANS]
        pick = min(mine or over) if over else (0, tracered.REPLAY)
        out.append([pick[1], g * 1e-9])
    return out


# ------------------------------------------------------------- readers ---

def _mean(devs, key):
    return sum(key(d) for d in devs) / len(devs)


def scope_ms_per_step(red: dict, scope: str, steps: int) -> float | None:
    """Device ms per step of the ops under `scope`, mean over devices;
    None where no op of the scope ran."""
    devs = (red or {}).get("devices") or []
    if not devs or not steps or not any(d["scope_ns"][scope] for d in devs):
        return None
    return 1e-6 * _mean(devs, lambda d: d["scope_ns"][scope]) / steps


def solve_launches_per_step(red: dict, steps: int) -> float | None:
    devs = (red or {}).get("devices") or []
    if not devs or not steps or not any(d["solve_launches"] for d in devs):
        return None
    return _mean(devs, lambda d: d["solve_launches"]) / steps


def host_bound_idle_share(red: dict) -> float | None:
    """100 x host-bound device idle over replay time, mean over devices;
    None where the trace holds no drive.wait span (a program without the
    drive loop's spans)."""
    devs = (red or {}).get("devices") or []
    window = (red or {}).get("window_ns") or 0.0
    if not devs or window <= 0 or not red.get("drive_spans"):
        return None
    return 100.0 * _mean(devs, lambda d: d["host_bound_idle_ns"]) / window


def metrics(trace: dict, scopes: dict, steps: int) -> dict:
    """The four per-layer numbers of a trace, None where the trace or
    the program has nothing to read."""
    red = reduce(trace, scopes)
    return {
        "solve_scope_ms_per_step": scope_ms_per_step(red, "solve", steps),
        "phases_scope_ms_per_step": scope_ms_per_step(red, "phases", steps),
        "solve_launches_per_step": solve_launches_per_step(red, steps),
        "host_bound_idle_share": host_bound_idle_share(red),
    }
