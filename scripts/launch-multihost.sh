#!/bin/bash
# Multi-process launcher — the ≙ of `mpirun -n N ./exe-<TAG> <args>` (how the
# reference exercises multi-node locally: oversubscribed mpirun, SURVEY.md §4).
# Starts N python processes that join one jax.distributed process group via
# the PAMPI_COORDINATOR/PAMPI_NPROCS/PAMPI_PROC_ID triple
# (pampi_tpu/parallel/multihost.py); the device mesh then spans all
# processes and the solvers run unchanged.
#
# SCOPE: virtual CPU devices, or real hosts with ONE process per host. A TPU
# chip belongs to one process (the first to touch JAX holds it), so N > 1 on
# one host is for PAMPI_LOCAL_DEVICES runs only; on a TPU host, one process
# drives all of that host's chips (python -m pampi_tpu with tpu_mesh).
#
# Local testing (no pod): PAMPI_LOCAL_DEVICES=K gives each process K virtual
# CPU devices, so `PAMPI_LOCAL_DEVICES=2 launch-multihost.sh 2 foo.par` runs
# the same 4-device mesh the tests fake in one process. On a real multi-host
# slice, run this once per host with the GLOBAL layout pinned:
#   PAMPI_COORDINATOR=<host0>:<port>          same on every host
#   PAMPI_TOTAL_PROCS=<hosts * procs_per_host> global process count
#   PAMPI_PROC_OFFSET=<host_rank * procs_per_host>
#   N=<procs on this host>
# or set PAMPI_MULTIHOST=auto per process and let the cloud runtime wire
# jax.distributed.initialize itself.
#
# Usage: [PAMPI_LOCAL_DEVICES=K] scripts/launch-multihost.sh N <cli args...>
set -u
# stay in the CALLER's directory (outputs and logs land there, like mpirun);
# the repo root is only needed as an import root
REPO=$(cd "$(dirname "$0")/.." && pwd)
[ $# -ge 2 ] || { echo "usage: launch-multihost.sh N <cli args...>" >&2; exit 2; }
N=$1; shift

# Coordinator port: take a flock on a per-port lockfile and HOLD it for the
# script's lifetime (fd 9), so concurrent launches on one host can never pick
# the same port (bind-and-release alone is a TOCTOU race). The bind probe
# only filters ports busied by unrelated processes.
# Base port/range overridable for operators who must move off the
# contended default (29500 is also torch.distributed's well-known default):
# PAMPI_PORT_BASE=<port> [PAMPI_PORT_RANGE=<n>] (round-2 advisor finding)
PORT_BASE=${PAMPI_PORT_BASE:-29500}
PORT_RANGE=${PAMPI_PORT_RANGE:-64}
if [ -z "${PAMPI_COORDINATOR:-}" ]; then
    if command -v flock >/dev/null 2>&1; then
        PORT=""
        for slot in $(seq 0 $(( PORT_RANGE - 1 ))); do
            CAND=$(( PORT_BASE + slot ))
            exec 9> "${TMPDIR:-/tmp}/pampi-port-$CAND.lock"
            if flock -n 9 && python -c "import socket; s=socket.socket(); s.bind(('127.0.0.1',$CAND)); s.close()" 2>/dev/null; then
                PORT=$CAND; break
            fi
            exec 9>&-
        done
        [ -n "$PORT" ] || { echo "launch-multihost.sh: no free coordinator port in $PORT_BASE-$(( PORT_BASE + PORT_RANGE - 1 )) (override with PAMPI_PORT_BASE/PAMPI_PORT_RANGE)" >&2; exit 1; }
    else
        # no flock on this host: fall back to bind-and-release (racy only
        # against concurrent launches in the same instant)
        PORT=$(python -c 'import socket; s=socket.socket(); s.bind(("127.0.0.1",0)); print(s.getsockname()[1]); s.close()')
    fi
fi
COORD=${PAMPI_COORDINATOR:-127.0.0.1:$PORT}
OFFSET=${PAMPI_PROC_OFFSET:-0}
TOTAL=${PAMPI_TOTAL_PROCS:-$N}   # global count; defaults to single-host N

# PYTHONPATH is REPLACED for virtual-CPU runs, so nothing inherited can
# steer the platform away from JAX_PLATFORMS=cpu; extra import roots go in
# PAMPI_PYTHONPATH.
PIDS=()
for p in $(seq 0 $(( N - 1 ))); do
    if [ -n "${PAMPI_LOCAL_DEVICES:-}" ]; then
        env PAMPI_COORDINATOR="$COORD" PAMPI_NPROCS="$TOTAL" \
            PAMPI_PROC_ID=$(( OFFSET + p )) \
            JAX_PLATFORMS=cpu \
            XLA_FLAGS="--xla_force_host_platform_device_count=$PAMPI_LOCAL_DEVICES" \
            PYTHONPATH="$REPO${PAMPI_PYTHONPATH:+:$PAMPI_PYTHONPATH}" \
            python -m pampi_tpu "$@" > "multihost-r$(( OFFSET + p )).log" 2>&1 &
    else
        env PAMPI_COORDINATOR="$COORD" PAMPI_NPROCS="$TOTAL" \
            PAMPI_PROC_ID=$(( OFFSET + p )) \
            PYTHONPATH="$REPO${PYTHONPATH:+:$PYTHONPATH}" \
            python -m pampi_tpu "$@" > "multihost-r$(( OFFSET + p )).log" 2>&1 &
    fi
    PIDS+=($!)
done

FAIL=0
for p in $(seq 0 $(( N - 1 ))); do
    wait "${PIDS[$p]}" || { FAIL=1; echo "rank $(( OFFSET + p )) FAILED (multihost-r$(( OFFSET + p )).log):" >&2
                            tail -5 "multihost-r$(( OFFSET + p )).log" >&2; }
done
cat "multihost-r$OFFSET.log"
exit $FAIL
