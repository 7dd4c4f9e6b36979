#!/usr/bin/env bash
# Dynamic membership acceptance: a 1x2 party trains while an OUT-OF-PLAN
# third worker joins mid-training (ADD_NODE), trains a couple of rounds,
# and leaves gracefully (ref: runtime id assignment van.cc:41-112).
#
# MODE=tsengine or MODE=hfa runs the same join under the TS overlay /
# the HFA weight-averaging loop (r5: membership is uniform across
# modes, like the reference's ADD_NODE).
set -euo pipefail
MODE="${MODE:-}"
EXTRA=()
case "$MODE" in
  tsengine) EXTRA+=(--tsengine) ;;
  hfa)      EXTRA+=(--hfa) ;;
  "")       ;;
  *) echo "unknown MODE='$MODE' (want tsengine|hfa|empty)" >&2; exit 2 ;;
esac
HERE="$(cd "$(dirname "$0")" && pwd)"
cd "$HERE/.."
export JAX_PLATFORMS=cpu  # several role processes share this host; a chip belongs to one (see run_cluster.sh)
BASE_PORT="${BASE_PORT:-9400}"
STEPS="${STEPS:-8}"
# the joiner's rounds must be a PREFIX of the cluster's (it folds into
# the count; rounds past the cluster's last would stall against
# terminated servers) — clamp its steps under the cluster's
JOIN_STEPS=2
if [ "$STEPS" -lt 3 ]; then JOIN_STEPS=1; fi

PARTIES=1 WORKERS=2 STEPS="$STEPS" BASE_PORT="$BASE_PORT" \
  "$HERE/run_cluster.sh" "${EXTRA[@]}" &
CLUSTER=$!
# a joiner crash must not orphan the 6 cluster processes (they would
# hold the ports forever waiting for the dead joiner's rounds)
trap 'kill "$CLUSTER" 2>/dev/null || true' EXIT
sleep 2
python -m geomx_tpu.launch --role worker:2@p0 --parties 1 --workers 2 \
  --base-port "$BASE_PORT" --steps "$JOIN_STEPS" --join \
  "${EXTRA[@]}" \
  --advertise "127.0.0.1:$((BASE_PORT + 40))"
wait "$CLUSTER"
trap - EXIT
