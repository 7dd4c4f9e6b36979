#!/usr/bin/env bash
# Partition-tolerance demo (ISSUE 16): a real OS-process TCP cluster
# rides out a region-sized WAN outage without evicting anyone.
#
# Party 0's local server carries a scripted GEOMX_NETFAULT_PLAN: ~25 s
# into its life the plan blackholes that process's own outbound WAN
# sends (heartbeats included) for 12 s — the in-fabric equivalent of a
# regional uplink dying, no iptables required.  Asserted, in order:
#
#   1. the global scheduler QUARANTINES party 0 (indirect probe through
#      the party's own scheduler still hears it) — never the legacy
#      "folded party 0 out" fold, and no worker eviction anywhere;
#   2. the stranded server enters DEGRADED mode and keeps closing local
#      rounds, accumulating a catch-up delta;
#   3. on heal it ships the staleness-stamped catch-up delta (no dense
#      warm boot) and the party folds back into global rounds;
#   4. training completes end to end on every worker.
#
# Env: BASE_PORT (9600), STEPS (120)
set -euo pipefail
cd "$(dirname "$0")/.."
export JAX_PLATFORMS=cpu  # several role processes share this host; a chip belongs to one (see run_cluster.sh)

BASE_PORT="${BASE_PORT:-9600}"
STEPS="${STEPS:-120}"
LOG_DIR="$(mktemp -d)"
export GEOMX_PARTITION_MODE=1
export GEOMX_HEARTBEAT_INTERVAL="${GEOMX_HEARTBEAT_INTERVAL:-0.5}"
export GEOMX_HEARTBEAT_TIMEOUT="${GEOMX_HEARTBEAT_TIMEOUT:-2.5}"
export GEOMX_REQUEST_RETRY_S="${GEOMX_REQUEST_RETRY_S:-1.0}"
export GEOMX_PARTITION_DEGRADE_S="${GEOMX_PARTITION_DEGRADE_S:-2.5}"
export GEOMX_PARTITION_CATCHUP_BOUND="${GEOMX_PARTITION_CATCHUP_BOUND:-10000}"
# keep every worker stepping ~300 ms so the outage window lands
# provably mid-training and steps remain after the heal; --sync mixed
# decouples the parties so the survivor's rounds keep closing while
# party 0 is dark
export GEOMX_TEST_STEP_SLEEP_MS='{"worker:0@p0": 300, "worker:0@p1": 300}'

# the fault tape, applied ONLY inside party 0's server process: cut its
# WAN links 25 s after boot (past configure + the first jit'ed steps),
# heal 12 s later
NETFAULT_PLAN='[{"at_s": 25.0, "duration_s": 12.0,
                 "kind": "party_blackhole", "party": 0}]'

COMMON=(--parties 2 --workers 1 --base-port "$BASE_PORT" \
        --steps "$STEPS" --sync mixed)

pids=()
declare -A PID_OF
launch() {  # launch <role> [extra env as K=V ...]
  local role="$1"; shift
  env "$@" python -m geomx_tpu.launch --role "$role" "${COMMON[@]}" \
    >"$LOG_DIR/${role//[:@]/_}.log" 2>&1 &
  pids+=($!)
  PID_OF["$role"]=$!
}

launch "global_scheduler:0"
launch "global_server:0"
launch "scheduler:0@p0"
launch "server:0@p0" GEOMX_NETFAULT_PLAN="$NETFAULT_PLAN"
launch "worker:0@p0"
launch "scheduler:0@p1"
launch "server:0@p1"
launch "worker:0@p1"
trap 'kill "${pids[@]}" 2>/dev/null || true; rm -rf "$LOG_DIR"' EXIT

wait_for_log() {  # wait_for_log <file> <pattern> <tries>
  for _ in $(seq 1 "$3"); do
    grep -q "$2" "$LOG_DIR/$1" 2>/dev/null && return 0
    sleep 0.5
  done
  echo "TIMEOUT waiting for '$2' in $1"; tail -5 "$LOG_DIR/$1" || true
  return 1
}

wait_for_log "worker_0_p0.log" "configured — training begins" 300
echo ">>> training running; waiting for the scripted blackhole"

# ---- 1. the cut lands; detection says QUARANTINE, not eviction --------
wait_for_log "server_0_p0.log" "netfault cut party_blackhole party:0" 120
echo ">>> party 0's WAN uplink is dark"
wait_for_log "global_scheduler_0.log" "quarantined party 0" 60
if grep -q "folded party 0 out of global rounds" \
    "$LOG_DIR/global_scheduler_0.log"; then
  echo "FAIL: the partition took the legacy fold path"
  exit 1
fi
if grep -hq "evicted worker" "$LOG_DIR"/*.log; then
  echo "FAIL: the partition evicted a worker"
  exit 1
fi
echo ">>> quarantined, nobody evicted"

# ---- 2. degraded rounds behind the cut --------------------------------
wait_for_log "server_0_p0.log" "entered degraded mode" 60
echo ">>> party 0 is in degraded rounds (delta accumulating)"

# ---- 3. heal → catch-up re-merge, no dense resync ---------------------
wait_for_log "server_0_p0.log" "netfault heal party_blackhole party:0" 60
wait_for_log "server_0_p0.log" "shipped catch-up delta" 120
wait_for_log "global_scheduler_0.log" \
  "party 0 healed.*rejoined via catchup" 60
if grep -q "warm-booted" "$LOG_DIR/global_scheduler_0.log"; then
  echo "FAIL: the heal dense-resynced instead of catching up"
  exit 1
fi
echo ">>> catch-up delta merged; party 0 back in global rounds"

# ---- 4. training completes on every worker ----------------------------
fail=0
for role in "worker:0@p0" "worker:0@p1"; do
  wait "${PID_OF[$role]}" || fail=1
  grep -q "steps=" "$LOG_DIR/${role//[:@]/_}.log" || fail=1
done
if grep -hq "quarantine escalated to a fold\|evicted worker" \
    "$LOG_DIR"/*.log; then
  echo "FAIL: quarantine did not hold for the whole outage"
  fail=1
fi

echo "=== summary ==="
grep -h "netfault\|quarantined\|degraded mode\|catch-up\|healed" \
  "$LOG_DIR"/*.log | sort -u || true
echo "partition demo exit=$fail"
exit $fail
