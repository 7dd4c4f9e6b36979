#!/usr/bin/env bash
# Elastic-membership churn demo (ISSUE 13): a real OS-process TCP
# cluster under spot-preemption semantics.
#
#   1. SIGTERM one worker — the launch.py preempt mapping turns it
#      into a graceful drain: the worker finishes its step, flushes,
#      leaves the party, and the server folds it out IMMEDIATELY.
#      Asserted: the drain marker appears and the eviction monitor
#      NEVER fires for that worker.
#   2. SIGKILL one party's local server mid-run — the ungraceful path
#      is unchanged: the global scheduler folds the party out, a
#      relaunched replacement warm-boots, the party folds back in, and
#      training completes end to end.
#
# Env: BASE_PORT (9500), STEPS (40)
set -euo pipefail
cd "$(dirname "$0")/.."
export JAX_PLATFORMS=cpu  # several role processes share this host; a chip belongs to one (see run_cluster.sh)

BASE_PORT="${BASE_PORT:-9500}"
STEPS="${STEPS:-100}"
LOG_DIR="$(mktemp -d)"
export GEOMX_PREEMPT_NOTICE=1
export GEOMX_HEARTBEAT_INTERVAL="${GEOMX_HEARTBEAT_INTERVAL:-0.5}"
export GEOMX_HEARTBEAT_TIMEOUT="${GEOMX_HEARTBEAT_TIMEOUT:-2.5}"
export GEOMX_REQUEST_RETRY_S="${GEOMX_REQUEST_RETRY_S:-1.0}"
# pace party 0 well behind party 1 so both fault windows land
# mid-training AND party 1 (outage included) finishes before party 0's
# rank-0 worker ends the run; --sync mixed decouples the parties'
# progress (a sync-global run would drag the recovered party along at
# party 0's pace and invert the finish order)
export GEOMX_TEST_STEP_SLEEP_MS='{"worker:0@p0": 700, "worker:1@p0": 700,
                                  "worker:0@p1": 300, "worker:1@p1": 300}'

COMMON=(--parties 2 --workers 2 --base-port "$BASE_PORT" \
        --steps "$STEPS" --sync mixed)

pids=()
declare -A PID_OF
launch() {
  local role="$1"
  python -m geomx_tpu.launch --role "$role" "${COMMON[@]}" \
    >"$LOG_DIR/${role//[:@]/_}.log" 2>&1 &
  pids+=($!)
  PID_OF["$role"]=$!
}

launch "global_scheduler:0"
launch "global_server:0"
for p in 0 1; do
  launch "scheduler:0@p$p"
  launch "server:0@p$p"
  launch "worker:0@p$p"
  launch "worker:1@p$p"
done
trap 'kill "${pids[@]}" 2>/dev/null || true; rm -rf "$LOG_DIR"' EXIT

wait_for_log() {  # wait_for_log <file> <pattern> <tries>
  for _ in $(seq 1 "$3"); do
    grep -q "$2" "$LOG_DIR/$1" 2>/dev/null && return 0
    sleep 0.5
  done
  echo "TIMEOUT waiting for '$2' in $1"; tail -5 "$LOG_DIR/$1" || true
  return 1
}

wait_for_log "worker_1_p1.log" "configured — training begins" 300
sleep 4  # past the first-step jit compile, provably mid-training

# ---- 1. graceful preemption: SIGTERM = the notice ---------------------
VICTIM="worker:1@p1"
echo ">>> SIGTERM $VICTIM (pid ${PID_OF[$VICTIM]}) — the preempt notice"
kill -TERM "${PID_OF[$VICTIM]}"
wait_for_log "worker_1_p1.log" "preempted — drained and left gracefully" 120
if grep -q "evicted worker:1@p1" "$LOG_DIR"/*.log; then
  echo "FAIL: the noticed worker fired the eviction monitor"
  exit 1
fi
echo ">>> graceful fold confirmed: drained, folded, never evicted"

# ---- 2. ungraceful preemption: SIGKILL a local server mid-round -------
sleep 1
SRV="server:0@p1"
echo ">>> SIGKILL $SRV (pid ${PID_OF[$SRV]}) — the eviction path"
kill -9 "${PID_OF[$SRV]}"
wait_for_log "global_scheduler_0.log" "folded party 1 out of global rounds" 60
echo ">>> relaunching $SRV"
launch "$SRV"
if ! wait_for_log "global_scheduler_0.log" "party 1 recovered" 300; then
  echo "--- diagnostics: relaunched server log"
  tail -20 "$LOG_DIR/server_0_p1.log" || true
  echo "--- diagnostics: global scheduler log"
  tail -20 "$LOG_DIR/global_scheduler_0.log" || true
  exit 1
fi
wait_for_log "worker_0_p1.log" "party server recovered" 120

# ---- training completes on every surviving worker ---------------------
fail=0
for role in "worker:0@p0" "worker:1@p0" "worker:0@p1"; do
  wait "${PID_OF[$role]}" || fail=1
  grep -q "steps=" "$LOG_DIR/${role//[:@]/_}.log" || fail=1
done
wait "${PID_OF[$VICTIM]}" || fail=1  # the drained worker exited cleanly

echo "=== summary ==="
grep -h "preempted — drained\|folded party\|party 1 recovered\|evicted" \
  "$LOG_DIR"/*.log | sort -u || true
echo "churn demo exit=$fail"
exit $fail
