"""Coordinated multi-host fault handling: the agree-then-act protocol.

The reference MPI stack has no fault story — one rank dies and the whole
`MPI_Cart` job dies with it — and the PR 4 recovery layer was explicitly
single-controller: multi-process dist runs passed `transient_budget=0`
because a rank-local retry would desynchronize the chunk's collectives
across ranks. This module closes that gap the way the partitioned-MPI
literature structures it (PAPERS.md, "Persistent and Partitioned MPI for
Stencil Communication"): the chunk boundary — where the host already
syncs on the loop time — is the one safe rendezvous, so that is where
ranks agree.

Protocol (one round per chunk boundary):

1. Every rank dispatches the same chunk and builds a small integer FAULT
   WORD from what it observed locally: done flag, transient-fault flag,
   pallas-fallback flag, divergence flag, proposed rollback generation
   (the newest ring-captured `nt`), checkpoint vote.
2. The words are allgathered (`multihost_utils.process_allgather` — a
   host-side collective of WORD_LEN ints, nothing rides the traced
   programs) and merged with fixed per-slot min/max reductions, so every
   rank holds the identical merged word.
3. Every rank takes the SAME decision deterministically from the merged
   word: re-dispatch the same chunk on a transient (the budget is now
   GLOBAL — one rank's hiccup spends everyone's charge, replenish
   semantics unchanged), fall back to the jnp chunk together on a pallas
   failure, roll back to the agreed RingRecovery generation on a
   divergence, commit a checkpoint on a vote, finish when ALL ranks are
   past te. A rank that is locally done keeps joining the allgather
   (dispatching device no-op chunks) until the merged word says done —
   the DONE path never leaves a peer blocked in the collective.

DEAD RANKS (PR 12): the boundary allgather is WATCHDOG-TIMED — a rank
that stops answering (process death, wedged host) no longer hangs its
peers at the rendezvous until the backend's opaque timeout. The watchdog
(`tpu_coord_timeout` seconds; the utils/xlacache probe pattern — the
blocking collect runs on a daemon thread with a hard join timeout) fires
on every survivor at the same boundary; the survivors then run one
MEMBERSHIP AGREEMENT round over the surviving set — each submits an
epoch-tagged word whose dead-rank bitmask is OR-merged — so every
survivor lands on the identical DEAD verdict and the identical
incremented shrink epoch, and raises the same structured `RankDeadError`
naming the lost rank(s). Words are EPOCH-TAGGED (W_EPOCH): a stale
straggler word from before a shrink can never merge into a post-shrink
round (apply() aborts on skew). Recovery is the shrink-to-survivors
resume layer (fleet/scheduler.shrink_resume: re-init on the survivor
set, rebuild the solver on the shrunk mesh, restore the newest agreed
elastic generation + the persisted fault ledger). Remaining window: a
rank that dies INSIDE a chunk's device collectives still waits out the
backend's own collective timeout before its peers reach the boundary —
the watchdog owns the HOST-side rendezvous. The verdict + shrink epoch
+ elastic resume chain is tier-1-proven on the LockstepSim virtual-rank
path (a dead virtual rank simply stops producing words —
`dead@chunk<N>@rank<R>` / `hang@chunk<N>@rank<R>` clauses); the real
kill-a-process acceptance case is capability-gated in
tests/test_multihost.py.

The seam is `models/_driver.drive_chunks(coordinator=...)`: None (the
single-process default) is the exact historical host loop, and the
protocol itself is host-side only — all CONTRACTS.json jaxpr hashes are
unchanged.

Two coordinator transports share the one loop (`CoordinatedLoop`, an
explicit boundary state machine):

- `MultihostCoordinator` — the real cross-process allgather (TPU/GPU
  pods; CPU only with a gloo jaxlib — `multihost.multiprocess_capable`).
- `LockstepSim` — N virtual ranks driven in lockstep inside ONE process
  (each rank a full solver instance built under
  `faultinject.rank_scope`), merging words with the same reduction the
  allgather path uses. This is what makes the agree-then-act logic,
  the global budget accounting and the rollback agreement
  tier-1-testable on this CPU container (tests/test_coordinator.py);
  `tests/test_multihost.py` holds the real multi-process acceptance
  cases that un-gate on capable hardware.

Every global decision is a flight-recorder line: telemetry `coord`
records (schema v5), emitted once per decision from rank 0.

CONSUMERS of the RankDeadError verdict: besides the CLI surfaces
(tools/serve_elastic.py-style operator flows and the test harnesses),
the serving daemon's autopilot (fleet/autopilot.py, PR 19) subscribes
to it as a POLICY INPUT — a verdict raised by a resident elastic job is
turned into an automatic `shrink_resume` onto survivor capacity (fault
ledger carried through the elastic manifest), no operator in the loop.
The protocol's guarantee that every survivor raises the IDENTICAL
structured verdict is what makes that safe to automate.
"""

from __future__ import annotations

import warnings

import numpy as np

from ..utils import faultinject as _fi
from ..utils import profiling as _prof
from ..utils import telemetry as _tm

# the fault word: one int64 per slot, merged elementwise with _MERGE_OPS.
# W_ROLLBACK_NT proposes the newest ring-captured step count; NO_ROLLBACK
# (merge-neutral under min) means "nothing to roll back to here".
# W_EPOCH tags the word with the sender's shrink epoch (uniform by
# construction — apply() aborts on skew, the stale-straggler guard);
# W_DEADMASK is the membership round's payload: a bitmask of the ranks
# this sender observed dead, OR-merged so the survivors' union IS the
# agreed verdict (ranks 0..62 — the real transport's membership round
# goes through the coordination-service KV store, not the mask).
(W_DONE, W_FAULT, W_FALLBACK, W_DIVERGED, W_ROLLBACK_NT, W_CKPT,
 W_EPOCH, W_DEADMASK) = range(8)
WORD_LEN = 8
NO_ROLLBACK = np.int64(2**62)


def _or_reduce(col):
    return np.bitwise_or.reduce(np.asarray(col, np.int64))


_MERGE_OPS = (np.min, np.max, np.max, np.max, np.min, np.max,
              np.max, _or_reduce)

# the watchdog default: well under the backend collective timeouts
# (XLA's cross-host barriers sit at 10+ minutes) so a dead rank is agreed
# at the HOST rendezvous first, and generous enough that a straggler
# paying a cold compile inside its chunk is never misdeclared dead.
DEFAULT_WATCHDOG_S = 300.0


class CoordinatorAbort(RuntimeError):
    """The agreed decision is to abort: the global transient budget is
    exhausted (or a peer hit a fault this rank cannot act on). Raised on
    EVERY rank at the same boundary, so the job dies cleanly instead of
    one rank dying inside a collective with its peers blocked."""


class RankDeadError(RuntimeError):
    """A rank stopped answering the boundary allgather: the watchdog
    fired and the survivors' membership agreement round produced this —
    the SAME verdict, on every survivor, at the same boundary. Carries
    the agreed dead set (`ranks`; empty when the transport could not
    attribute the timeout to specific ranks), the post-shrink `epoch`,
    the surviving ranks and the boundary index. The structured recovery
    is the shrink-to-survivors resume: restore the newest agreed elastic
    checkpoint generation onto the survivor set
    (fleet/scheduler.shrink_resume; cli.py catches this exception when
    `tpu_dead_resume` is armed)."""

    def __init__(self, ranks=(), epoch=None, boundary=None, family="",
                 survivors=None, reason=""):
        self.ranks = sorted(int(r) for r in ranks)
        self.epoch = epoch
        self.boundary = boundary
        self.family = family
        self.survivors = (None if survivors is None
                          else sorted(int(r) for r in survivors))
        self.reason = reason
        super().__init__()

    def __str__(self) -> str:
        # composed late: drive_coordinated annotates boundary/family
        # after the transport raised
        who = (f"rank(s) {self.ranks}" if self.ranks
               else "unattributed rank(s) (allgather timed out)")
        return (f"{self.family or 'coordinated run'}: DEAD {who} at "
                f"boundary {self.boundary} — survivors agreed shrink "
                f"epoch {self.epoch}"
                + (f"; {self.reason}" if self.reason else "")
                + "; resume on the survivor set from the newest elastic "
                  "checkpoint generation (fleet/scheduler.shrink_resume)")


def dead_mask(ranks) -> int:
    """Encode a dead-rank set as the W_DEADMASK bitmask (ranks 0..62)."""
    m = 0
    for r in ranks:
        if not 0 <= int(r) < 63:
            raise ValueError(f"W_DEADMASK encodes ranks 0..62, got {r}")
        m |= 1 << int(r)
    return m


def mask_ranks(mask: int) -> list:
    """Decode a W_DEADMASK bitmask back to the sorted rank list."""
    return [r for r in range(63) if (int(mask) >> r) & 1]


def blank_word() -> np.ndarray:
    w = np.zeros(WORD_LEN, np.int64)
    w[W_ROLLBACK_NT] = NO_ROLLBACK
    return w


def merge_words(words) -> np.ndarray:
    """The one merge rule both transports share: elementwise fixed
    reductions over the (nranks, WORD_LEN) matrix — min for done (all
    ranks must be past te) and the rollback target (every rank can dig
    to the shallowest common generation), max for the fault/divergence/
    vote flags (any rank's fault is everyone's fault)."""
    mat = np.asarray(words, np.int64).reshape(-1, WORD_LEN)
    return np.asarray(
        [op(mat[:, i]) for i, op in enumerate(_MERGE_OPS)], np.int64
    )


class SoloCoordinator:
    """1-rank coordinator (`tpu_coord on` under a single process): the
    merged word IS the local word. Exists so the production protocol
    path can be exercised — and kept bitwise-identical to the
    uncoordinated loop — without a multi-process launch."""

    nranks = 1
    rank = 0

    def agree(self, word: np.ndarray) -> np.ndarray:
        return merge_words(word)


class MultihostCoordinator:
    """The real transport: allgather the WORD_LEN-int fault word across
    OS processes at each chunk boundary. The allgather is itself a
    collective — which is exactly why every decision below it must be
    taken identically everywhere, and why locally-done ranks keep
    joining it until the merged word says done.

    WATCHDOG (PR 12): the allgather runs on a daemon thread with a hard
    `timeout` join (0 disables — the pre-watchdog hang-until-backend
    behavior). On expiry every surviving rank raises RankDeadError at
    the same boundary; the dead set is attributed best-effort through
    the coordination-service KV store (each survivor posts an
    epoch-tagged liveness key and reads its peers' with the same grace
    window — a rank that never posts is dead). Attribution failing
    (older jax, no KV client) degrades to an EMPTY dead set with the
    timeout named in the reason — structured and loud either way, never
    a wedge. The abandoned allgather thread is a daemon: it dies with
    the process, exactly the xlacache probe contract."""

    def __init__(self, timeout: float = DEFAULT_WATCHDOG_S):
        import jax

        self.nranks = jax.process_count()
        self.rank = jax.process_index()
        self.timeout = timeout
        self._round = 0  # agree rounds so far (keys the membership round)

    def agree(self, word: np.ndarray) -> np.ndarray:
        from jax.experimental import multihost_utils

        self._round += 1
        if not self.timeout or self.timeout <= 0:
            return merge_words(
                np.asarray(multihost_utils.process_allgather(word)))
        import threading

        box: dict = {}

        def gather():
            try:
                box["mat"] = np.asarray(
                    multihost_utils.process_allgather(word))
            except Exception as exc:  # lint: allow(broad-except) — surfaced on the driving thread below
                box["exc"] = exc

        t = threading.Thread(target=gather, daemon=True,
                             name=f"pampi-coord-agree-{self._round}")
        t.start()
        t.join(self.timeout)
        if t.is_alive():
            dead = self._membership_round()
            survivors = ([r for r in range(self.nranks) if r not in dead]
                         if dead else None)
            epoch = int(word[W_EPOCH]) + 1
            # the flight-recorder `dead` line is emitted by
            # drive_coordinated's handler, where boundary/family are
            # known — one record shape for both transports
            raise RankDeadError(
                ranks=dead or (), epoch=epoch, survivors=survivors,
                reason=(f"boundary allgather exceeded the "
                        f"{self.timeout:g}s watchdog"),
            )
        if "exc" in box:
            raise box["exc"]
        return merge_words(box["mat"])

    def _membership_round(self) -> list:
        """Best-effort dead-set attribution over the jax coordination
        service's KV store: post my liveness key for this round, then
        blocking-read every rank's against ONE shared deadline a
        watchdog window and a half out — a rank that never posts is
        dead. The watchdog is the documented bound on an honest rank's
        lag (`tpu_coord_timeout` must exceed the slowest honest chunk),
        so survivors enter this round at most one window apart; the
        extra half window is the margin for KV round-trips and
        scheduling latency, without which a rank arriving exactly one
        window late would post AT the deadline and be misdeclared. The
        verdict is still BEST-EFFORT — a rank slower than the knob it
        was configured with can be misdeclared, which is the knob's
        documented contract, and the cross-process resume stays
        operator-driven (cli.py prints the walkthrough; nothing
        auto-resumes on a possibly-split verdict). Returns [] when the
        KV client is unreachable on this jax."""
        import time

        try:
            from jax._src import distributed

            client = distributed.global_state.client
            if client is None:
                return []
            prefix = f"pampi_coord/alive/round{self._round}"
            client.key_value_set(f"{prefix}/r{self.rank}", "1")
            # one deadline for the WHOLE read set: N dead ranks must not
            # cost N grace windows (each get consumes remaining budget)
            deadline = time.monotonic() + 1.5 * max(self.timeout, 1.0)
            dead = []
            for r in range(self.nranks):
                left_ms = max(1, int((deadline - time.monotonic()) * 1e3))
                try:
                    client.blocking_key_value_get(
                        f"{prefix}/r{r}", left_ms)
                except Exception:  # lint: allow(broad-except) — a missing key IS the verdict; any get failure reads as dead
                    dead.append(r)
            return dead
        except Exception:  # lint: allow(broad-except) — attribution is best-effort; the structured RankDeadError fires regardless
            return []


class CoordinatedLoop:
    """One rank's chunked drive loop as an explicit boundary machine:
    `local_word()` dispatches the next chunk and reports what happened;
    `apply(merged)` acts on the agreed decision. `drive_coordinated`
    wires the two around a coordinator's `agree`; `LockstepSim` advances
    N of these in lockstep with the same merge.

    Semantics mirror `models/_driver.drive_chunks` with three deliberate
    deviations, all protocol-forced: lookahead pipelining is off (every
    boundary is a rendezvous), the transient budget is GLOBAL (any
    rank's fault spends the shared charge; replenish-after-clean-chunks
    unchanged), and the pallas->jnp fallback / restore runs on EVERY
    rank at the same boundary (a lone rank changing its compiled program
    would desynchronize the collectives the fallback exists to save)."""

    def __init__(self, state, chunk_fn, te, time_index, bar, retry,
                 on_state=None, replenish_after: int = 8, recover=None,
                 transient_budget: int = 1, rank: int = 0,
                 ckpt_every: int = 0, on_ckpt=None, family: str = "",
                 watchdog: float = DEFAULT_WATCHDOG_S, ledger=None):
        self.chunk_fn = chunk_fn
        self.te = te
        self.time_index = time_index
        self.bar = bar
        self.retry = retry
        self.on_state = on_state
        self.replenish_after = replenish_after
        self.recover = recover
        self.rank = int(rank)
        self.ckpt_every = max(0, int(ckpt_every))
        self.on_ckpt = on_ckpt
        self.family = family
        self.watchdog = watchdog
        self.on_final = None  # optional publish-back hook (LockstepSim)
        self.final = None

        self._confirmed = state
        self._pending = None
        self._t_pending = None
        self._budget = max(0, int(transient_budget))
        self._max_budget = self._budget
        # the restored fault ledger (utils/checkpoint elastic manifest):
        # a resumed fleet starts with the SPENT budget and the shrink
        # epoch it died with, rank-symmetrically — every rank read the
        # same manifest
        ledger = ledger or {}
        self.epoch = int(ledger.get("epoch", 0))
        spent = max(0, int(ledger.get("budget_spent", 0)))
        self._budget = max(0, self._budget - spent)
        self._clean = 0
        self._boundary = 0  # agreed boundaries so far (rounds of agree)
        self._confirms = 0  # confirmed (clean) chunks — the ckpt cadence
        self._local_done = float(state[time_index]) > te
        self._local_exc = None
        self._took_fallback = False  # this rank already swapped this round

    # -- step 1: dispatch + observe -----------------------------------
    def local_word(self) -> np.ndarray:
        """Dispatch the next chunk (a device no-op once past te) and
        report the local observation. Never acts — every action waits
        for the merged word."""
        w = blank_word()
        w[W_EPOCH] = self.epoch
        self._local_exc = None
        self._took_fallback = False
        if self.final is not None or self._local_done:
            w[W_DONE] = 1
            return w
        try:
            with _fi.rank_scope(self.rank):
                _fi.maybe_chunk_fault()  # injected fault plane (test-only)
                with _prof.region("drive.dispatch"):
                    pending = self.chunk_fn(*self._confirmed)
                # force completion: async runtime faults surface here
                with _prof.region("drive.wait"):
                    t = float(pending[self.time_index])
        except Exception as exc:  # lint: allow(broad-except) — the fault-classification funnel, same contract as drive_chunks
            if isinstance(exc, _fi.FaultSpecError):
                raise  # a broken TEST spec fails loudly, never classified
            self._pending = None
            self._local_exc = exc
            from ..models._driver import _is_transient_device_fault

            if _is_transient_device_fault(exc):
                w[W_FAULT] = 1
                return w
            new_fn = self.retry()
            if new_fn is None:
                raise  # no alternative program: a genuine error kills
                # the job on this rank; peers abort at the next agree
                # round when the allgather dies with it
            self.chunk_fn = new_fn
            self._took_fallback = True
            w[W_FALLBACK] = 1
            return w
        self._pending = pending
        self._t_pending = t
        diverged = t != t or (
            self.recover is not None and self.recover.poisoned(pending)
        )
        if diverged:
            w[W_DIVERGED] = 1
            if self.recover is not None:
                nt = self.recover.newest_nt()
                if nt >= 0:
                    w[W_ROLLBACK_NT] = nt
        elif t > self.te:
            w[W_DONE] = 1
        if (self.on_ckpt is not None and self.ckpt_every > 0
                and not diverged
                and (self._confirms + 1) % self.ckpt_every == 0):
            w[W_CKPT] = 1
        return w

    # -- step 3: the one decision, taken identically everywhere -------
    def apply(self, merged: np.ndarray) -> None:
        if self.final is not None:
            return
        if int(merged[W_EPOCH]) != self.epoch:
            # a stale word from before a shrink leaked into this round —
            # the merge is undefined across epochs, so die loudly rather
            # than act on a verdict half the fleet never saw
            raise CoordinatorAbort(
                f"{self.family}: epoch skew in the merged fault word "
                f"(merged epoch {int(merged[W_EPOCH])}, this rank's "
                f"epoch {self.epoch}) at boundary {self._boundary}"
            )
        self._boundary += 1
        if merged[W_FALLBACK]:
            self._apply_fallback()
            return
        if merged[W_FAULT]:
            self._apply_transient()
            return
        if merged[W_DIVERGED]:
            self._apply_rollback(merged)
            return
        self._apply_confirm(merged)

    def _reset_streak(self) -> None:
        self._clean = 0
        reset_clean = getattr(self.retry, "reset_clean", None)
        if reset_clean is not None:
            reset_clean()

    def _emit(self, event: str, **fields) -> None:
        """One flight-recorder line per GLOBAL decision (rank 0 only —
        the word is identical everywhere by construction)."""
        if self.rank == 0:
            _tm.emit("coord", event=event, boundary=self._boundary,
                     family=self.family, **fields)

    def _apply_fallback(self) -> None:
        """A pallas runtime failure somewhere: every rank swaps to its
        jnp rebuild so the fleet keeps tracing ONE program. Ranks whose
        dispatch succeeded discard the pending state (it ran the old
        program) and re-dispatch."""
        self._pending = None
        self._reset_streak()
        if not self._took_fallback:
            # a peer fell back; mirror it locally — EVERY rank that has
            # not already swapped must, including one that raised a
            # transient in the same round (guarding on "did I raise
            # anything" would leave that rank on the pallas program and
            # desynchronize the fleet's traced programs). retry() on a
            # healthy rank rebuilds the same jnp chunk (and shares the
            # deterministically-broken probation accounting, which stays
            # rank-symmetric because every transition is agreed).
            new_fn = self.retry()
            if new_fn is None:
                raise CoordinatorAbort(
                    f"{self.family}: a peer rank took the pallas->jnp "
                    "fallback but this rank has no alternative chunk "
                    "program — configs have desynchronized"
                )
            self.chunk_fn = new_fn
        self._emit("fallback")

    def _apply_transient(self) -> None:
        """A transient device fault somewhere: all ranks re-dispatch the
        same chunk (inputs unchanged — the loop is functional) on one
        shared, replenishing budget."""
        self._pending = None
        self._reset_streak()
        if self._budget <= 0:
            self._emit("abort", reason="transient budget exhausted")
            raise CoordinatorAbort(
                f"{self.family}: global transient budget exhausted at "
                f"boundary {self._boundary}"
            ) from self._local_exc
        self._budget -= 1
        warnings.warn(
            f"{self.family}: transient device fault on a rank; all ranks "
            f"retrying the chunk (global budget left {self._budget})",
            stacklevel=2,
        )
        self._emit("retry", budget_left=self._budget,
                   t=float(self._confirmed[self.time_index]))

    def _apply_rollback(self, merged: np.ndarray) -> None:
        """A divergence somewhere: every rank rolls back to the AGREED
        generation (the merged min of the proposed ring entries) and
        re-drives with the same clamped dt — or, when no rank has a
        state to offer (or recovery is exhausted), every rank terminates
        on its diverged state exactly like the single-controller loop."""
        target = int(merged[W_ROLLBACK_NT])
        rolled = None
        if self.recover is not None and target < int(NO_ROLLBACK):
            rolled = self.recover.attempt(target_nt=target)
        if rolled is None:
            self._emit("giveup",
                       target_nt=None if target >= int(NO_ROLLBACK)
                       else target)
            self.final = (self._pending if self._pending is not None
                          else self._confirmed)
            self._finish()
            return
        state_rb, new_fn = rolled
        self._confirmed = state_rb
        self._pending = None
        self.chunk_fn = new_fn
        self._reset_streak()
        self._emit("rollback", target_nt=target,
                   t=float(state_rb[self.time_index]))

    def _apply_confirm(self, merged: np.ndarray) -> None:
        if self._pending is not None:
            self._confirmed = self._pending
            self._pending = None
            self._confirms += 1
            self._clean += 1
            if (self.replenish_after > 0
                    and self._clean >= self.replenish_after
                    and self._budget < self._max_budget):
                self._budget = self._max_budget
            with _prof.region("drive.sync"):
                restore = getattr(self.retry, "on_clean_chunk", None)
                if restore is not None:
                    # deterministic on every rank: the clean streak advances
                    # at agreed boundaries only, so all ranks restore their
                    # pallas chunk at the SAME boundary
                    restored_fn = restore()
                    if restored_fn is not None:
                        self.chunk_fn = restored_fn
                if self.bar is not None:
                    self.bar.update(self._t_pending)
                if self.on_state is not None:
                    self.on_state(self._confirmed)
                if merged[W_CKPT] and self.on_ckpt is not None:
                    self._emit("ckpt", t=self._t_pending)
                    if getattr(self.on_ckpt, "takes_ledger", False):
                        # the coordinated writer persists the fault ledger
                        # into the elastic manifest alongside the fields
                        # (models/_driver.coord_ckpt_cadence marks itself)
                        self.on_ckpt(self._confirmed, ledger=self.ledger())
                    else:
                        self.on_ckpt(self._confirmed)
            if self._t_pending > self.te:
                self._local_done = True
        if merged[W_DONE]:
            self.final = self._confirmed
            self._finish()

    def _finish(self) -> None:
        if self.bar is not None:
            self.bar.stop()
        if self.on_final is not None:
            self.on_final(self.final)

    def ledger(self) -> dict:
        """The FAULT LEDGER: the protocol state a restarted/shrunk fleet
        must not forget — spent global transient budget, the pallas
        probation verdict (a deterministically-broken kernel stays
        broken across a restart), the divergence-recovery attempts +
        cumulative dt clamp, and the shrink epoch. Persisted into the
        elastic manifest at every agreed checkpoint commit
        (utils/checkpoint.save_elastic) and restored rank-symmetrically
        by load_elastic — every rank reads the same manifest, so the
        restored state can never skew."""
        led = {
            "budget_spent": int(self._max_budget - self._budget),
            "epoch": int(self.epoch),
        }
        pallas_ledger = getattr(self.retry, "ledger", None)
        if pallas_ledger is not None:
            led["pallas"] = pallas_ledger()
        if self.recover is not None:
            led["recover_attempts"] = int(self.recover._attempts)
            led["dt_scale"] = float(
                getattr(self.recover.solver, "_dt_scale", 1.0))
        return led


def drive_coordinated(state, chunk_fn, te, time_index, bar, retry,
                      coordinator, on_state=None, replenish_after: int = 8,
                      recover=None, transient_budget: int = 1,
                      ckpt_every: int = 0, on_ckpt=None, family: str = "",
                      ledger=None):
    """The coordinated drive loop: one CoordinatedLoop per rank, one
    `agree` round per chunk boundary. Entered through
    `models/_driver.drive_chunks(coordinator=...)`. A RankDeadError from
    the transport's watchdog is annotated with this loop's boundary and
    family, the progress bar stopped, and re-raised — the resume layer
    (cli.py / fleet.scheduler.shrink_resume) owns what happens next."""
    loop = CoordinatedLoop(
        state, chunk_fn, te, time_index, bar, retry, on_state=on_state,
        replenish_after=replenish_after, recover=recover,
        transient_budget=transient_budget, rank=coordinator.rank,
        ckpt_every=ckpt_every, on_ckpt=on_ckpt, family=family,
        watchdog=getattr(coordinator, "timeout", DEFAULT_WATCHDOG_S),
        ledger=ledger,
    )
    while loop.final is None:
        try:
            merged = coordinator.agree(loop.local_word())
        except RankDeadError as exc:
            if exc.boundary is None:
                exc.boundary = loop._boundary
            if not exc.family:
                exc.family = family
            # the transport raises bare (it knows neither boundary nor
            # family); the flight-recorder line lands here so both
            # transports' `dead` records carry the same fields
            _tm.emit("dead", ranks=exc.ranks or None, epoch=exc.epoch,
                     boundary=exc.boundary, family=exc.family,
                     nranks=coordinator.nranks,
                     watchdog_s=getattr(coordinator, "timeout", None))
            if exc.survivors is not None:
                _tm.emit("epoch", epoch=exc.epoch,
                         nranks=len(exc.survivors),
                         survivors=exc.survivors)
            if bar is not None:
                bar.stop()
            raise
        loop.apply(merged)
    stash = getattr(on_ckpt, "stash_ledger", None)
    if stash is not None:
        # the agreed-done ledger survives even when the run finished
        # before the first cadence commit: the cli's end-of-run elastic
        # write reads it back via save_elastic's _fault_ledger fallback
        stash(loop.ledger())
    return loop.final


class LockstepSim:
    """N virtual ranks in ONE process: every round, collect each rank's
    local word, merge with the same reduction the allgather transport
    uses, apply everywhere. A rank here is a full solver instance (a
    replica, built under `faultinject.rank_scope(r)` so rank-targeted
    clauses arm only their target) — the collective coupling of a real
    mesh is replaced by the replicas' determinism, which is exactly what
    lets the agree-then-act logic be proven on one CPU.

    DEAD RANKS: each rank's word is collected under the WATCHDOG — the
    dispatch runs on a daemon thread with a hard join timeout (ranks
    stay SEQUENTIAL: the virtual-rank fault counters are process
    globals, and determinism is the whole point). A rank that raises
    InjectedRankDeath (`dead@chunk<N>@rank<R>`) or overruns the window
    (`hang@chunk<N>@rank<R>`) produces no word; the survivors then run
    the membership agreement round — the same epoch-tagged OR-merge the
    word protocol uses — and every survivor raises the identical
    RankDeadError. This is the tier-1 proof of the dead-rank protocol;
    the abandoned hung thread is a daemon and dies with the process."""

    def __init__(self, loops, watchdog: float | None = None):
        self.loops = list(loops)
        # None: take the per-loop watchdog (sim_rank_loop wires it from
        # the .par tpu_coord_timeout key)
        self.watchdog = watchdog

    def _window(self) -> float:
        if self.watchdog is not None:
            return self.watchdog
        return getattr(self.loops[0], "watchdog", DEFAULT_WATCHDOG_S)

    def _collect_word(self, loop):
        """One rank's local_word under the watchdog; None = this rank is
        dead (stopped answering or overran the window). Any other
        exception re-raises on the driving thread — the historical
        propagate-loudly contract."""
        import threading

        box: dict = {}

        def work():
            try:
                box["word"] = loop.local_word()
            except _fi.InjectedRankDeath:
                box["dead"] = True
            except BaseException as exc:  # lint: allow(broad-except) — ferried to the driving thread and re-raised there
                box["exc"] = exc

        t = threading.Thread(target=work, daemon=True,
                             name=f"pampi-sim-rank{loop.rank}")
        t.start()
        window = self._window()
        t.join(window if window and window > 0 else None)
        if t.is_alive() or box.get("dead"):
            return None
        if "exc" in box:
            raise box["exc"]
        return box["word"]

    def _declare_dead(self, dead_ranks, survivors):
        """The membership agreement round: every survivor submits an
        epoch-tagged word carrying its observed dead-rank bitmask; the
        OR-merge is the agreed verdict, the incremented epoch the agreed
        shrink — then every survivor raises the same RankDeadError."""
        if not survivors:
            # total fleet loss (an untargeted dead clause): nothing left
            # to agree with — one structured error instead of a merge of
            # zero words. Hung sleepers still unwind NOW: an abandoned
            # hang thread exiting its rank_scope later would restore the
            # ambient-rank global mid-way through the next test's builds
            _fi.cancel_hangs()
            raise RankDeadError(
                ranks=dead_ranks, epoch=self.loops[0].epoch + 1,
                survivors=[], reason="no survivors")
        words = []
        for loop in survivors:
            w = blank_word()
            w[W_EPOCH] = loop.epoch
            w[W_DEADMASK] = dead_mask(dead_ranks)
            words.append(w)
        merged = merge_words(np.stack(words))
        ranks = mask_ranks(int(merged[W_DEADMASK]))
        epoch = int(merged[W_EPOCH]) + 1
        boundary = survivors[0]._boundary if survivors else None
        _fi.cancel_hangs()  # the verdict is in; hung sleepers may unwind
        for loop in survivors:
            loop.epoch = epoch
            if loop.bar is not None:
                loop.bar.stop()
        _tm.emit("dead", ranks=ranks, epoch=epoch,
                 boundary=boundary, nranks=len(self.loops),
                 watchdog_s=self._window(),
                 family=survivors[0].family if survivors else "")
        _tm.emit("epoch", epoch=epoch, nranks=len(survivors),
                 survivors=[loop.rank for loop in survivors])
        raise RankDeadError(
            ranks=ranks, epoch=epoch, boundary=boundary,
            family=survivors[0].family if survivors else "",
            survivors=[loop.rank for loop in survivors],
        )

    def run(self) -> list:
        """Drive all ranks to agreement-confirmed completion; returns
        the per-rank final states. A CoordinatorAbort (or an unhandled
        fault) on any rank propagates — the job dies, it never hangs;
        a dead/hung rank raises RankDeadError on the survivors within
        one watchdog window per rank."""
        while any(loop.final is None for loop in self.loops):
            words, dead = [], []
            for loop in self.loops:
                w = self._collect_word(loop)
                if w is None:
                    dead.append(loop.rank)
                else:
                    words.append(w)
            if dead:
                self._declare_dead(
                    dead,
                    [lp for lp in self.loops if lp.rank not in dead])
            merged = merge_words(np.stack(words))
            for loop in self.loops:
                loop.apply(merged)
        return [loop.final for loop in self.loops]


def sim_rank_loop(solver, family: str, time_index: int, rank: int,
                  te=None, transient_budget: int = 1,
                  replenish_after: int = 8, ckpt_every: int = 0,
                  on_ckpt=None) -> CoordinatedLoop:
    """Build one virtual rank's CoordinatedLoop over a solver instance,
    mirroring the solver run() wiring (per-rank ChunkRecorder tagged
    through the telemetry scenario dimension, ring recovery from the
    .par keys, publish-back of the final state). The simulation's
    constructor — build the solver itself under
    `faultinject.rank_scope(rank)` first so rank-targeted field faults
    bake only into their target."""
    from ..models._driver import make_recovery

    rec = (_tm.ChunkRecorder(family, solver.nt, scenario=f"rank{rank}")
           if getattr(solver, "_metrics", False) else None)
    recover = make_recovery(solver, family, time_index, recorder=rec)
    state = solver.initial_state()
    if recover is not None:
        recover.capture(state)  # first-chunk divergence is recoverable

    n_fields = time_index
    names = ("u", "v", "p") if n_fields == 3 else ("u", "v", "w", "p")

    def publish(s):
        for name, value in zip(names, s[:n_fields]):
            setattr(solver, name, value)
        solver.t = float(s[time_index])
        solver.nt = int(s[time_index + 1])

    def on_state(s):
        if rec is not None:
            rec.update(float(s[time_index]), int(s[time_index + 1]),
                       s[time_index + 2])
        if recover is not None:
            recover.capture(s)

    chunk_fn = getattr(solver, "_chunk_sm", None) or solver._chunk_fn
    loop = CoordinatedLoop(
        state, chunk_fn, solver.param.te if te is None else te,
        time_index, bar=None, retry=lambda: None, on_state=on_state,
        replenish_after=replenish_after, recover=recover,
        transient_budget=transient_budget, rank=rank,
        ckpt_every=ckpt_every, on_ckpt=on_ckpt, family=family,
        watchdog=getattr(solver.param, "tpu_coord_timeout",
                         DEFAULT_WATCHDOG_S),
        ledger=getattr(solver, "_fault_ledger", None),
    )
    loop.on_final = publish
    return loop


def coord_armed(param) -> bool:
    """Side-effect-free predicate of `make_coordinator`'s answer — the
    cli asks it before wiring the single-controller periodic checkpoint
    writer (the coordinated loop owns the cadence itself, through the
    agreed checkpoint vote)."""
    import jax

    knob = getattr(param, "tpu_coord", "auto")
    if knob == "off":
        return False
    return jax.process_count() > 1 or knob == "on"


def make_coordinator(param, family: str):
    """The `tpu_coord` knob -> a coordinator or None (utils/dispatch
    records the decision like every other knob): `auto` arms the
    multihost transport under a multi-process launch and nothing
    otherwise — so a single-process run's drive loop is the exact
    historical path; `on` forces the protocol through the 1-rank
    SoloCoordinator (the seam-identity proof shape); `off` restores the
    PR 4 guard (multi-process runs get transient_budget=0 and a fault
    kills the job cleanly)."""
    from ..utils import dispatch as _dispatch

    mode = _dispatch.resolve_coord(param, f"coord_{family}")
    if mode == "none":
        return None
    coord = (MultihostCoordinator(
                 timeout=getattr(param, "tpu_coord_timeout",
                                 DEFAULT_WATCHDOG_S))
             if mode == "multihost" else SoloCoordinator())
    _tm.emit("coord", event="armed", family=family, mode=mode,
             nranks=coord.nranks, rank=coord.rank)
    return coord
