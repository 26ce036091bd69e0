"""Persistent XLA compilation cache.

The heaviest fixed cost of a chip run is compilation: a recompile of an
unchanged program is a disk load once JAX's persistent cache holds it.
The CLI, bench.py and chip_smoke.py call `enable()` before their first
compile.

  JAX_COMPILATION_CACHE_DIR=<dir>   JAX's own variable: the cache lives
                                    there, on every backend, and no code
                                    here names another path
  (unset)                           accelerator runs cache in the fixed
                                    `<checkout>/.jax_cache` (gitignored;
                                    the path is part of the cache key, so
                                    it must not move between runs); CPU
                                    runs stay uncached
  JAX_ENABLE_COMPILATION_CACHE=0    JAX's own switch: no cache at all
  PAMPI_XLA_CACHE_TIMEOUT           cache-dir reachability probe budget
                                    in seconds (default 5; 0 skips it)

Multi-process launches share the directory; the cache is content-addressed
and concurrent-access safe. The directory is PROBED (with a hard timeout)
before it is handed to XLA: on a shared filesystem a dead NFS/GCS mount —
or the documented wedge below, where one rank's cache access hangs while
its peers block inside a collective waiting for it — must degrade to a
warn-and-run-uncached, never to a hung fleet. The probe failure emits a
structured telemetry `warning` record, so a silently-slow serving process
names its own degradation in the flight record.
"""

from __future__ import annotations

import os
import warnings

CHECKOUT_CACHE = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__)))), ".jax_cache")


def cache_dir(backend: str) -> str | None:
    """The cache directory a run on `backend` uses, or None for none.

    CPU runs are uncached unless JAX_COMPILATION_CACHE_DIR is set: a
    cached XLA:CPU AOT executable records the exact machine-feature set of
    the compiling context — loading it from a context with different
    XLA/compile flags fails ("+prefer-no-gather is not supported on the
    host machine") and can wedge a multi-process run with one rank dead and
    its peers blocked in a collective (observed)."""
    from . import flags as _flags

    val = _flags.env("JAX_COMPILATION_CACHE_DIR",
                     doc="XLA compilation-cache dir (JAX's own variable); "
                         "unset = <checkout>/.jax_cache on accelerators, "
                         "none on CPU")
    if val:
        return val
    return None if backend == "cpu" else CHECKOUT_CACHE


def enable() -> str | None:
    """Turn the cache on; returns the directory, or None when disabled or
    unavailable. Call before the first compilation."""
    import jax

    from . import flags as _flags

    if not jax.config.jax_enable_compilation_cache:
        return None
    path = cache_dir(jax.default_backend())
    if path is None:
        return None
    try:
        timeout = float(_flags.env(
            "PAMPI_XLA_CACHE_TIMEOUT", "5",
            doc="cache-dir reachability probe budget, seconds (0 skips)"))
    except ValueError:
        timeout = 5.0
    reason = _probe_dir(path, timeout) if timeout > 0 else None
    if reason is not None:
        # the wedge guard: a dead rank (or dead shared storage) must not
        # leave peers blocked on the cache path — proceed UNCACHED with a
        # loud, structured degradation notice instead (JAX may already
        # hold the path from its own variable, so switch the cache off)
        from . import telemetry as _tm

        warnings.warn(
            f"XLA compilation cache at {path!r} is unusable ({reason}); "
            "proceeding UNCACHED — compiles will pay full cost this run",
            stacklevel=2,
        )
        _tm.emit("warning", component="xlacache", reason=reason, path=path)
        jax.config.update("jax_enable_compilation_cache", False)
        return None
    try:
        os.makedirs(path, exist_ok=True)
        # min-compile-time first, dir last: until the dir is set nothing is
        # persisted, so a failure between the two leaves the cache fully OFF
        # (cache everything that took real compile time; trivial programs
        # aren't worth the disk round-trip)
        jax.config.update("jax_persistent_cache_min_compile_time_secs", 1.0)
        # a Pallas kernel's serialized body carries its MLIR locations, so
        # with traceback frames every call stack that reaches a kernel is a
        # key of its own (the same chunk lowered from two call sites gave
        # two keys, and the chip smoke's second compile missed): keep no
        # frame, and one program keeps one key. Full-traceback locations
        # with a zero frame limit still carry the name stack, so op_name
        # keeps the `solve`/`phases` scope path and every Pallas kernel
        # its `name=` (the innermost-frame mode drops both)
        jax.config.update("jax_include_full_tracebacks_in_locations", True)
        jax.config.update("jax_traceback_in_locations_limit", 0)
        jax.config.update("jax_compilation_cache_dir", path)
    except (OSError, AttributeError):
        return None
    return path


def _probe_dir(path: str, timeout_s: float):
    """Reachability probe with a HARD timeout: create + write + remove a
    marker in the cache dir on a daemon thread, give it `timeout_s`.
    Returns None when healthy, else the reason string. A hung shared
    mount makes plain os calls block indefinitely — the thread is the
    only portable way to bound that (the blocked thread is abandoned;
    daemon threads die with the process)."""
    import threading

    err: list = []

    def probe():
        try:
            os.makedirs(path, exist_ok=True)
            marker = os.path.join(path, f".pampi-probe-{os.getpid()}")
            with open(marker, "w") as fh:
                fh.write("ok")
            os.remove(marker)
        except OSError as exc:
            err.append(f"cache dir unusable ({exc})")

    t = threading.Thread(target=probe, daemon=True)
    t.start()
    t.join(timeout_s)
    if t.is_alive():
        return (f"cache-dir probe exceeded {timeout_s:g}s "
                "(hung shared storage?)")
    return err[0] if err else None
