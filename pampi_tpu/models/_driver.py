"""Shared chunked time-loop driver for the NS solvers.

All four NS families advance a carried state tuple through jitted chunk
calls (CHUNK device steps per host sync) with the same failure-handling
protocol, and this module is that protocol's single home — the solvers
supply the state arity, the rebuild hook, and the ring-capture callback:

- pallas runtime failure: a shape-specific fault the dispatcher probe
  missed rebuilds the chunk on the jnp path (same arithmetic) and retries
  the chunk — inputs are unchanged because the loop is functional. After
  `restore_after` consecutive clean chunks on the fallback, the pallas
  chunk is rebuilt and restored (a 10-hour run should not pay jnp speed
  forever for one transient kernel fault); a pallas that breaks again
  right after a restore is treated as deterministically broken and stays
  on jnp.
- transient `UNAVAILABLE` device fault: one same-chunk retry, with a
  budget that REFILLS after `replenish_after` consecutive clean chunks
  (PR 4; previously one per run — satellite fix).
- divergence: a NaN loop time is terminal for the loop, but when a
  `RingRecovery` is armed (tpu_recover_ring > 0) the loop rolls back to
  the last captured finite state and re-drives with a clamped dt instead
  of terminating.

Every consumption emits a structured telemetry record (`retry` /
`recover`); the injection plane (`utils/faultinject.py`, PAMPI_FAULTS)
forges each fault class deterministically so tests prove the protocol
end-to-end.
"""

from __future__ import annotations

import math
import warnings
from collections import deque

import jax

from ..utils import faultinject as _fi
from ..utils import profiling as _prof
from ..utils import telemetry as _tm


def _is_transient_device_fault(exc) -> bool:
    """An UNAVAILABLE device error is the one runtime fault worth a
    same-chunk retry (the loop is functional, so the inputs are
    unchanged); anything else is a real error. Every retry is a telemetry
    `retry` record."""
    return type(exc).__name__ == "JaxRuntimeError" and "UNAVAILABLE" in str(exc)


def clamped_dt(dt, scale):
    """Trace-time dt clamp for rollback-recovery rebuilds: every family's
    step multiplies its computed (or constant) dt through here. Identity —
    the SAME tracer, zero added ops — at the default scale 1.0, so the
    uninjected/unrecovered trace is byte-identical."""
    if scale == 1.0:
        return dt
    import jax.numpy as jnp

    return dt * jnp.asarray(scale, dt.dtype)


def drive_chunks(state, chunk_fn, te, time_index, bar, retry, on_state=None,
                 lookahead: int = 0, replenish_after: int = 8, recover=None,
                 transient_budget: int = 1, coordinator=None,
                 ckpt_every: int = 0, on_ckpt=None, family: str = "",
                 ledger=None):
    """Run `state = chunk_fn(*state)` while state[time_index] <= te
    (main.c:43-60 loop semantics: a step runs whenever t <= te at its start).

    retry() is called when a chunk raises: it returns a rebuilt chunk_fn to
    retry with, or None if there is no alternative path (the failure was not
    pallas's). In the None case a TRANSIENT device fault still gets one
    same-chunk retry (inputs are unchanged — the loop is functional) before
    re-raising; the transient budget refills after `replenish_after`
    consecutive clean chunks (0 = never — the historical one-per-run
    budget); `transient_budget=0` disables the transient retry entirely
    (the multi-process dist case: a rank-local re-dispatch would
    desynchronize collectives across ranks — let the error kill the job
    cleanly instead). If retry has an `on_clean_chunk()` hook
    (pallas_retry), it is
    consulted after every confirmed chunk and may hand back a restored
    pallas chunk_fn. on_state(state) fires after every successful chunk —
    the host-sync / checkpoint / ring-capture hook point. Returns the final
    state (the first whose time exceeds te).

    recover, when not None, is a RingRecovery: a confirmed NaN loop time
    (adaptive-dt blow-up) OR a fired in-band divergence sentinel (field-only
    blow-up under telemetry) triggers recover.attempt() — roll back to the
    last captured finite state, clamp dt, re-drive — instead of returning
    the diverged state; the loop only lands on the diverged state
    terminally once the recovery gives up (attempts exhausted / nothing to
    roll back to).

    lookahead > 0 pipelines the dispatch: up to lookahead+1 chunks stay in
    flight (the one being confirmed plus `lookahead` queued behind it — so
    lookahead=0 is one in flight, the serial case) and the host reads the
    loop time only from the OLDEST of them,
    so the per-chunk host<->device round trip (the dominant end-to-end cost
    on the retired remote-chip setup of round 4 — 27.7 vs the chip's 12.7
    ms/step at dcavity 4096^2) overlaps the younger chunks' device
    execution. Safe
    by construction: a chunk dispatched past te is a device no-op (its own
    while-cond sees t > te and passes the state through), so speculative
    overshoot never advances the simulation, and the (undonated) input
    buffers stay alive for the retry path. On any failure the pipeline
    resets to the last CONFIRMED state — the retry protocol is unchanged,
    it just may re-dispatch the speculative tail. lookahead=0 is exactly
    the historical dispatch-then-sync loop.

    coordinator, when not None, routes the whole loop through the
    chunk-boundary agreement protocol (parallel/coordinator.py): ranks
    allgather a small fault word at each boundary and take every
    retry / rollback / checkpoint decision identically — the seam that
    lifts the multi-process `transient_budget=0` ban. None (the
    single-process default) is THIS exact loop, untouched. The
    coordinated path forces lookahead=0 (every boundary is a
    rendezvous) and takes the agreed checkpoint cadence from
    `ckpt_every`/`on_ckpt` instead of an on_state counter.

    ledger, when not None, is a restored FAULT LEDGER (the elastic
    manifest's `ledger` key, stashed on the solver by
    utils/checkpoint.load_elastic): the spent transient budget carries
    over so a resumed run starts with the charge it died with — the
    rank-symmetric no-amnesia contract (the pallas verdict and dt clamp
    were already re-applied at load time).

    Every chunk is three host spans (utils/profiling.region, in any active
    profiler trace): `drive.dispatch` around the chunk call, `drive.wait`
    around the fence that reads its loop time, `drive.sync` around the
    bookkeeping after a confirmation (restore hook, bar, ETA, on_state).
    The coordinated loop uses the same three names."""
    if coordinator is not None:
        from ..parallel.coordinator import drive_coordinated

        return drive_coordinated(
            state, chunk_fn, te, time_index, bar, retry,
            coordinator, on_state=on_state,
            replenish_after=replenish_after, recover=recover,
            transient_budget=transient_budget, ckpt_every=ckpt_every,
            on_ckpt=on_ckpt, family=family, ledger=ledger,
        )
    if lookahead < 0:
        # cli.py validates the .par key; programmatic callers land here (a
        # negative value would popleft an empty deque and surface an
        # IndexError through the device-fault retry path)
        raise ValueError(f"lookahead must be >= 0 (got {lookahead})")
    max_transient = max(0, transient_budget)  # replenish refills to THIS
    if ledger:
        # resumed run: start with the spent charge, refill to the full
        # budget on the usual clean streak
        transient_budget = max(
            0, transient_budget - int(ledger.get("budget_spent", 0)))
    clean = 0  # consecutive confirmed chunks since the last fault/recovery
    # per-chunk steps/s + ETA line behind PAMPI_PROFILE (utils/progress.
    # ChunkEta): a multi-minute run stops being a silent decile bar. The
    # state convention (..., t, nt[, metrics]) puts nt right after the
    # loop time (the make_recovery contract), so the line costs one tiny
    # scalar readback per chunk — and only when the flag is armed, on
    # process 0 only (the master-only emitter convention; N ranks
    # \r-redrawing one terminal would garble it).
    from ..utils.progress import ChunkEta

    eta = (ChunkEta(te)
           if _prof.enabled() and jax.process_index() == 0 else None)
    if eta is not None and hasattr(bar, "disable"):
        bar.disable()  # one \r-redrawn line at a time — the ETA wins
    if float(state[time_index]) > te:
        bar.stop()
        return state

    pending = deque()  # in-flight states, oldest first
    confirmed = state  # last state whose time read succeeded
    newest = state
    final = None
    while final is None:
        try:
            if len(pending) <= lookahead:
                _fi.maybe_chunk_fault()  # injected fault plane (test-only)
                with _prof.region("drive.dispatch"):
                    newest = chunk_fn(*newest)
                pending.append(newest)
                continue
            old = pending.popleft()
            # force completion of the oldest in-flight chunk: async pallas
            # faults surface here, overlapped with the younger dispatches
            with _prof.region("drive.wait"):
                t_old = float(old[time_index])
        except Exception as exc:  # lint: allow(broad-except) — the fault-classification funnel: every runtime error class routes to transient/pallas/raise below
            if isinstance(exc, _fi.FaultSpecError):
                raise  # a broken TEST spec fails loudly at the first hook
                # — never classified as a kernel fault or retried
            pending.clear()
            newest = confirmed
            clean = 0
            if _is_transient_device_fault(exc):
                # handled BEFORE (and never by) the pallas hook: a
                # transient UNAVAILABLE is a device hiccup, not a kernel
                # fault — it gets the same-chunk retry while the budget
                # lasts and RE-RAISES once exhausted. Routing it into the
                # pallas fallback would misclassify the fault and, after a
                # restore, trip _PallasRetry's deterministically-broken
                # latch on a healthy kernel.
                if transient_budget <= 0:
                    raise
                reset_clean = getattr(retry, "reset_clean", None)
                if reset_clean is not None:
                    reset_clean()  # the fault breaks the clean streak
                warnings.warn(
                    "transient TPU device fault; retrying the chunk once",
                    stacklevel=2,
                )
                transient_budget -= 1
                _tm.emit("retry", fault="transient",
                         budget_left=transient_budget,
                         t=float(confirmed[time_index]))
                continue
            # NOT reset_clean() first: retry() judges the post-restore
            # probation on the streak AS IT STOOD when the fault hit (it
            # zeroes its own counter on fallback) — resetting here would
            # make every post-restore failure look immediate and latch the
            # deterministically-broken verdict on a healthy kernel
            new_fn = retry()
            if new_fn is None:
                raise
            chunk_fn = new_fn
            continue
        # the host's bookkeeping after a confirmation, one span per chunk
        with _prof.region("drive.sync"):
            confirmed = old
            # a diverged chunk is NOT clean: judge it before the replenish /
            # restore accounting so a poisoned confirmation can neither refill
            # the transient budget nor trigger a pallas restore
            diverged = t_old != t_old or (
                recover is not None and recover.poisoned(old)
            )
            if not diverged:
                clean += 1
                if (replenish_after > 0 and clean >= replenish_after
                        and transient_budget < max_transient):
                    transient_budget = max_transient  # M clean chunks: refill
                restore = getattr(retry, "on_clean_chunk", None)
                if restore is not None:
                    restored_fn = restore()
                    if restored_fn is not None:
                        # in-flight jnp states stay valid — only future
                        # dispatches run the restored pallas chunk
                        chunk_fn = restored_fn
            bar.update(t_old)
            if eta is not None:
                eta.update(t_old, int(old[time_index + 1]))
            if on_state is not None:
                on_state(old)
        # NaN loop time is terminal, not "not yet past te": an adaptive-dt
        # blow-up makes dt and then t NaN, every subsequent chunk is a
        # device no-op (its while-cond sees NaN <= te false), and
        # `t_old > te` is false for NaN — without this the loop would spin
        # forever on no-op dispatches (the dist solvers' old `while t <= te`
        # behaved the same way). The telemetry sentinel, when enabled, has
        # already named the last-good step by the time we land here — and
        # an armed RingRecovery turns the termination into a rollback.
        # NaN t alone MISSES field-only blow-ups (cfl_dt's `where(umax > 0,
        # dx/umax, inf)` selects the finite branch on a NaN maximum, and
        # fixed-dt runs never touch t at all), so an armed recovery also
        # treats a fired in-band sentinel as divergence — the "nothing acts
        # on the sentinel" gap this layer exists to close.
        if diverged or t_old > te:
            if diverged and recover is not None:
                rolled = recover.attempt()
                if rolled is not None:
                    state_rb, new_fn = rolled
                    pending.clear()
                    confirmed = newest = state_rb
                    chunk_fn = new_fn
                    clean = 0
                    reset_clean = getattr(retry, "reset_clean", None)
                    if reset_clean is not None:
                        reset_clean()
                    continue
            # recovery off / gave up: terminate ON the diverged state (a
            # diagnostic-bearing early stop, never a hang on garbage)
            final = old
    if eta is not None:
        eta.stop()
    bar.stop()
    return final


class _PallasRetry:
    """The retry() hook for a solver with `_backend`/`_uses_pallas`/
    `_build_chunk`/`_chunk_fn`: falls back to the jnp chunk (same
    arithmetic) when the failing chunk contained a pallas kernel; a failure
    on the jnp path (or with pallas not even in play) returns None so the
    error propagates. Covers the FUSED step-phase chunk too: `_uses_pallas`
    reports the fused kernels, and `_build_chunk(backend="jnp")` both
    selects the jnp solve AND stands the fused phases down
    (resolve_fuse_phases' backend contract), so one fallback recovers from
    a failure in either kernel family.

    restore_after > 0 replenishes the budget: after that many consecutive
    clean chunks on the jnp fallback, the pallas chunk is rebuilt and
    restored (`on_clean_chunk`, called by drive_chunks per clean
    confirmation). A pallas that fails again before the next
    `restore_after` clean chunks is deterministically broken — no further
    restores, the run stays jnp. drive_chunks filters transient
    UNAVAILABLE faults BEFORE this hook, so the broken-latch only ever
    judges genuine kernel failures.

    The jnp rebuild deliberately does NOT advance the field-fault
    injection generation: the failing chunk's armed corruption (if any)
    stays baked, so a combined `pallas@chunkN,nan@stepM:f` spec cannot
    silently run uninjected (solvers consume generations in __init__ and
    `_rebuild_chunk` only)."""

    def __init__(self, solver, what: str, restore_after: int = 0):
        self.solver = solver
        self.what = what
        self.restore_after = restore_after
        self._orig_backend = solver._backend
        self._on_jnp = False   # currently running the fallback chunk
        self._restored = False  # current pallas period came from a restore
        self._dead = False     # pallas judged deterministically broken
        self._clean = 0        # clean chunks since the last transition
        # a restored fault ledger (utils/checkpoint._restore_ledger has
        # already parked the solver on jnp): the deterministically-broken
        # verdict survives the restart — no probation amnesia
        led = (getattr(solver, "_fault_ledger", None) or {}).get("pallas")
        if led and led.get("broken"):
            self._dead = True
            self._on_jnp = solver._backend == "jnp"

    def ledger(self) -> dict:
        """This hook's slice of the coordinator fault ledger
        (parallel/coordinator.CoordinatedLoop.ledger)."""
        return {"broken": bool(self._dead),
                "on_jnp": bool(self._on_jnp),
                "backend": self.solver._backend}

    def __call__(self):
        s = self.solver
        if s._backend == "jnp" or not s._uses_pallas():
            return None  # the failing chunk never ran pallas — genuine error
        if self._restored and self._clean < self.restore_after:
            self._dead = True  # broke again right after a restore
        warnings.warn(
            f"pallas {self.what} failed at runtime; retrying this chunk on "
            "the jnp path", stacklevel=2,
        )
        _tm.emit("retry", fault="pallas", action="jnp_fallback",
                 what=self.what)
        s._backend = "jnp"
        s._chunk_fn = jax.jit(s._build_chunk(backend="jnp"))
        self._on_jnp = True
        self._clean = 0
        return s._chunk_fn

    def on_clean_chunk(self):
        """Per confirmed chunk: once `restore_after` consecutive clean
        chunks ran on the jnp fallback, rebuild and return the pallas
        chunk; None otherwise."""
        self._clean += 1
        if (not self._on_jnp or self._dead or self.restore_after <= 0
                or self._clean < self.restore_after):
            return None
        warnings.warn(
            f"restoring the pallas {self.what} after {self._clean} clean "
            "chunks on the jnp fallback", stacklevel=2,
        )
        _tm.emit("retry", fault="pallas", action="pallas_restore",
                 what=self.what, clean_chunks=self._clean)
        s = self.solver
        s._backend = self._orig_backend
        s._chunk_fn = jax.jit(s._build_chunk(backend=self._orig_backend))
        self._on_jnp = False
        self._restored = True
        self._clean = 0
        return s._chunk_fn

    def reset_clean(self) -> None:
        """Any fault or rollback breaks the consecutive-clean streak
        (drive_chunks calls this alongside its own `clean = 0`)."""
        self._clean = 0


def pallas_retry(solver, what: str, restore_after: int = 0):
    """Build the pallas runtime-retry hook (see _PallasRetry)."""
    return _PallasRetry(solver, what, restore_after=restore_after)


class RingRecovery:
    """Divergence rollback-recovery: an in-memory ring of the last-K
    confirmed finite chunk states (the HOT tier — device-resident
    references, no disk round-trip on the capture path) over the on-disk
    `tpu_checkpoint` as the COLD tier. `capture(state)` is the solvers'
    on_state hook: it keeps a state only when its loop time is finite and
    (with telemetry armed) the in-band sentinel has not fired inside its
    chunk — the ring never holds a poisoned state. `attempt()` is called
    by drive_chunks when the loop confirms divergence (NaN loop time, or a
    fired sentinel when telemetry rides the chunk): pop the newest
    captured state (successive attempts dig progressively deeper — fields
    can rot before t goes NaN), clamp the solver's dt by `dt_scale`
    (cumulative), re-trace the chunk via the solver's `_rebuild_chunk`
    hook, and re-drive. Bounded by `max_attempts` per run; every attempt
    emits a structured `recover` telemetry record, and giving up returns
    the loop to the historical terminate-on-NaN path (a diagnostic, never
    a hang)."""

    def __init__(self, solver, family: str, time_index: int, ring: int = 4,
                 dt_scale: float = 0.5, max_attempts: int = 3,
                 metrics_index=None, recorder=None, ckpt_path: str = ""):
        self.solver = solver
        self.family = family
        self.time_index = time_index
        self.dt_scale = dt_scale
        self.max_attempts = max_attempts
        self.metrics_index = metrics_index
        self.recorder = recorder
        self.ckpt_path = ckpt_path
        self._ring = deque(maxlen=max(1, int(ring)))
        self._attempts = 0
        self._memo_state = None  # last state judged by poisoned()
        self._memo_bad = False

    def capture(self, state) -> None:
        if not math.isfinite(float(state[self.time_index])):
            return
        if self.poisoned(state):
            return  # sentinel fired inside this chunk: poisoned state
        self._ring.append(state)

    def poisoned(self, state) -> bool:
        """The in-band sentinel fired inside this confirmed chunk: fields
        went non-finite even though the loop time is still finite (fixed-dt
        blow-up, NaN velocity maxima taking cfl_dt's finite branch) — the
        divergence the NaN-t trigger alone misses. False when telemetry is
        off (no sentinel rides the chunk). The verdict is memoized per
        state object (identity, with a strong ref): the drive loop and
        capture() both judge every confirmed chunk, and the metrics
        readback should cost one device sync, not two."""
        if self.metrics_index is None:
            return False
        if self._memo_state is state:
            return self._memo_bad
        import numpy as np

        bad = float(np.asarray(state[self.metrics_index])[_tm.M_BAD]) >= 0
        self._memo_state, self._memo_bad = state, bad
        return bad

    def _cold_state(self):
        """Ring exhausted: restore the newest on-disk generation (which
        itself falls back to `.prev` on corruption) and rebuild the chunk
        state at the current arity via initial_state()."""
        if not self.ckpt_path:
            return None
        from ..utils import checkpoint as ckpt

        try:
            # load_any: the cold tier must read whichever format the
            # run's tpu_checkpoint writes (legacy .npz OR the elastic
            # manifest — tpu_ckpt_elastic routes saves, so the sniff
            # keeps rollback working under both)
            ckpt.load_any(self.ckpt_path, self.solver)
        except Exception as exc:  # lint: allow(broad-except) — a cold-tier restore failure of ANY class degrades to "no checkpoint", never kills recovery
            warnings.warn(
                f"{self.family}: cold-tier restore from "
                f"{self.ckpt_path!r} failed ({exc})", stacklevel=2,
            )
            return None
        if not math.isfinite(self.solver.t):
            # belt over save_checkpoint's non-finite refusal: re-driving
            # from a diverged checkpoint would re-diverge instantly and
            # burn every remaining attempt on the same garbage
            warnings.warn(
                f"{self.family}: cold-tier checkpoint {self.ckpt_path!r} "
                "holds a non-finite state; not rolling back to it",
                stacklevel=2,
            )
            return None
        return self.solver.initial_state()

    def newest_nt(self) -> int:
        """Step count of the newest ring-captured state, -1 when empty —
        the rollback generation this rank PROPOSES in the coordinator
        fault word (parallel/coordinator.py; the merged min is what every
        rank then rolls to)."""
        if not self._ring:
            return -1
        return int(self._ring[-1][self.time_index + 1])

    def attempt(self, target_nt=None):
        """Returns (rollback_state, rebuilt_chunk_fn), or None to let the
        loop terminate on the diverged state. `target_nt`, when given (the
        coordinator's AGREED generation), first discards ring entries
        newer than it, so every rank restores the same step count — the
        rank-symmetric rollback contract."""
        self._attempts += 1
        if target_nt is not None:
            while (self._ring
                   and int(self._ring[-1][self.time_index + 1]) > target_nt):
                self._ring.pop()
        if self._attempts > self.max_attempts:
            _tm.emit("recover", family=self.family, attempt=self._attempts,
                     gave_up=True, reason="max_attempts")
            warnings.warn(
                f"{self.family}: divergence recovery gave up after "
                f"{self.max_attempts} attempts; returning the diverged "
                "state", stacklevel=2,
            )
            return None
        if self._ring:
            state, source = self._ring.pop(), "ring"
        else:
            state, source = self._cold_state(), "disk"
            if state is None:
                _tm.emit("recover", family=self.family,
                         attempt=self._attempts, gave_up=True,
                         reason="no_state")
                warnings.warn(
                    f"{self.family}: divergence recovery has no finite "
                    "state to roll back to; returning the diverged state",
                    stacklevel=2,
                )
                return None
        s = self.solver
        s._dt_scale = getattr(s, "_dt_scale", 1.0) * self.dt_scale
        new_fn = s._rebuild_chunk()
        t = float(state[self.time_index])
        nt = int(state[self.time_index + 1])
        if self.recorder is not None:
            self.recorder.rearm(nt)  # re-baseline: nt rewinds on rollback
        _tm.emit("recover", family=self.family, attempt=self._attempts,
                 source=source, t=t, nt=nt, dt_scale=s._dt_scale)
        warnings.warn(
            f"{self.family}: solver state diverged; rolled back to "
            f"t={t:.6g} (step {nt}, {source}) and re-driving with dt "
            f"clamped x{s._dt_scale:g} (attempt {self._attempts}/"
            f"{self.max_attempts})", stacklevel=2,
        )
        return state, new_fn


def coord_ckpt_cadence(solver, coord, publish):
    """Checkpoint cadence under the coordinator: the agreed ckpt vote
    commits the write at the boundary every rank voted on (the cli's
    on_sync periodic writer stands down when the coordinator is armed —
    see cli.py; two counters over the same cadence would double-write).
    Returns (ckpt_every, on_ckpt) — (0, None) when uncoordinated or no
    checkpoint path is set. The returned on_ckpt takes the loop's fault
    ledger (marked via `takes_ledger`) and hands it to the writer, so
    every agreed elastic commit persists the protocol state alongside
    the fields."""
    param = solver.param
    if coord is None or not param.tpu_checkpoint:
        return 0, None
    from ..utils import checkpoint as _ckpt

    writer = _ckpt.writer_for(param)

    def on_ckpt(s, ledger=None):
        publish(s)
        # stash the agreed ledger on the solver too: the cli's
        # END-OF-RUN write goes through save_elastic's _fault_ledger
        # fallback, so the final manifest keeps the last agreed
        # protocol state instead of silently dropping it
        solver._fault_ledger = ledger
        writer(param.tpu_checkpoint, solver, ledger=ledger)

    on_ckpt.takes_ledger = True

    def stash_ledger(ledger):
        # completion stash (no write): a run that finishes before the
        # first cadence boundary never called on_ckpt, so without this
        # the end-of-run manifest would drop the ledger entirely and
        # fail the `ckpt_fsck --survivors` pre-flight
        solver._fault_ledger = ledger

    on_ckpt.stash_ledger = stash_ledger
    return max(1, param.tpu_ckpt_every), on_ckpt


def make_recovery(solver, family: str, time_index: int, recorder=None):
    """RingRecovery from the solver's .par recovery keys; None when the
    ring is not armed (tpu_recover_ring 0 — the default, the historical
    terminate-on-NaN behavior)."""
    param = solver.param
    ring = getattr(param, "tpu_recover_ring", 0)
    if ring <= 0:
        return None
    # every family's state is (..., t, nt[, metrics]): metrics sits two
    # past the loop time when the telemetry vector rides the chunk
    mi = time_index + 2 if getattr(solver, "_metrics", False) else None
    rec = RingRecovery(
        solver, family, time_index, ring=ring,
        dt_scale=param.tpu_recover_dt_scale,
        max_attempts=param.tpu_recover_max,
        metrics_index=mi, recorder=recorder,
        ckpt_path=getattr(param, "tpu_checkpoint", ""),
    )
    led = getattr(solver, "_fault_ledger", None) or {}
    # resumed run: the attempt budget carries over (the dt clamp was
    # re-applied at load time) — a fleet that died mid-recovery cannot
    # restart with a fresh allowance against the same divergence
    rec._attempts = int(led.get("recover_attempts", 0))
    return rec
