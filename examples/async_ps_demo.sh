#!/bin/sh
# Uncoordinated async-PS demo: 4 OS processes (no JAX coordinator), each
# training its own data blocks of a shared word2vec corpus against
# row-sharded async tables — the reference's defining workflow
# (mpirun -np 4 distributed_wordembedding), rebuilt TPU-native.
# Mirrors tests/we_async_worker.py, runnable by hand.
#
# The wire rides the native C++ transport when libmv_ps builds
# (on first use); MV_PS_NATIVE=0 ./async_ps_demo.sh forces
# the pure-python plane for an A/B.
set -e
cd "$(dirname "$0")/.."
# the workers live under tests/, so python's script-dir sys.path entry is
# tests/ — the repo root must come from PYTHONPATH
PYTHONPATH="$PWD${PYTHONPATH:+:$PYTHONPATH}"
export PYTHONPATH
# one host, four processes: each on the CPU backend (one chip can't be
# shared); the worker pins it in code too (tests/we_async_worker.py)
JAX_PLATFORMS=cpu
export JAX_PLATFORMS
RDV=$(mktemp -d)
PIDS=""
# kill stragglers before deleting their rendezvous dir (a crashed rank
# must not leave the others polling a vanished directory)
# `|| true`: set -e applies INSIDE the trap (dash), so a clean run —
# where every pid already exited and kill fails — would otherwise abort
# the trap mid-way (rc 1, rendezvous dir leaked)
trap 'kill $PIDS 2>/dev/null || true; rm -rf "$RDV"' EXIT
for RANK in 0 1 2 3; do
  python tests/we_async_worker.py "$RDV" 4 "$RANK" &
  PIDS="$PIDS $!"
done
# wait per-pid: a bare `wait` always exits 0, hiding worker crashes
for P in $PIDS; do
  wait "$P"
done
echo "async PS demo: 4 workers done (rendezvous $RDV)"
