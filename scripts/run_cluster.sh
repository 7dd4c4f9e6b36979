#!/usr/bin/env bash
# Generic pseudo-distributed launcher: stands up the full HiPS topology as
# local OS processes over TCP (the reference's scripts/cpu/run_*.sh matrix,
# ref: docs/source/pseudo-distributed-deployment.rst — 2 parties of
# scheduler+server+2 workers plus the central party).
#
# Usage: run_cluster.sh [extra geomx_tpu.launch flags...]
# Env:   PARTIES (2), WORKERS (2), GSERVERS (1), BASE_PORT (9300), STEPS (6)
set -euo pipefail
cd "$(dirname "$0")/.."
# CPU acceptance matrix: 2+2P+PW role processes share this host, and a
# chip belongs to one process — on a TPU host the first role to touch
# jax would take it and the rest would fail.  The chip path is one
# process per host (Simulation; docs/deployment.md).
export JAX_PLATFORMS=cpu

PARTIES="${PARTIES:-2}"
WORKERS="${WORKERS:-2}"
GSERVERS="${GSERVERS:-1}"
BASE_PORT="${BASE_PORT:-9300}"
STEPS="${STEPS:-6}"
EXTRA=("$@")

COMMON=(--parties "$PARTIES" --workers "$WORKERS" --global-servers "$GSERVERS"
        --base-port "$BASE_PORT" --steps "$STEPS")

pids=()
launch() {
  python -m geomx_tpu.launch --role "$1" "${COMMON[@]}" "${EXTRA[@]}" &
  pids+=($!)
}

launch "global_scheduler:0"
for ((g=0; g<GSERVERS; g++)); do launch "global_server:$g"; done
for ((p=0; p<PARTIES; p++)); do
  launch "scheduler:0@p$p"
  launch "server:0@p$p"
  for ((w=0; w<WORKERS; w++)); do launch "worker:$w@p$p"; done
done

trap 'kill "${pids[@]}" 2>/dev/null || true' EXIT
fail=0
for pid in "${pids[@]}"; do
  wait "$pid" || fail=1
done
exit $fail
