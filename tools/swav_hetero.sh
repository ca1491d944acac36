#!/bin/bash
# SwAV multi-peer run on mixed hardware in one host (VERDICT r4 #7): the
# TPU chip as one SwAV trainer peer (ResNet-50 multicrop, queue engaged)
# plus a slow CPU SwAV volunteer, an aux bandwidth donor (gradient template
# self-bootstrapped from the TPU peer's shared state) and the coordinator;
# one SIGKILL/rejoin churn event mid-run. The vision-side counterpart of
# tools/hetero_converge.sh — SURVEY §1's two-level scheme (in-slice psum +
# cross-peer DHT averaging) exercised on the SwAV workload for real.
#
# Usage:
#   CORPUS=/root/corpus RUN=/root/corpus/r5_swav TOTAL=4800 CHURN=2400 \
#     REJOIN=300 bash tools/swav_hetero.sh
set -u
REPO="$(cd "$(dirname "${BASH_SOURCE[0]}")/.." && pwd)"
export PYTHONPATH="$REPO${PYTHONPATH:+:$PYTHONPATH}"
# compile cache: <checkout>/.jax_cache unless JAX_COMPILATION_CACHE_DIR is
# set (dedloc_tpu/utils/backend.py)
CORPUS=${CORPUS:-/root/corpus}
RUN=${RUN:-$CORPUS/r5_swav}
PREFIX=${PREFIX:-swav5}
PORT=${PORT:-42000}
TPU_AVG_PORT=${TPU_AVG_PORT:-42011}
WINDOW=${WINDOW:-30}
TARGET=${TARGET:-16}           # solo recipe scale (r4 sustained run)
TOTAL=${TOTAL:-4800}
CHURN=${CHURN:-2400}
REJOIN=${REJOIN:-300}
SAVE_STEPS=${SAVE_STEPS:-50}
QUEUE_START=${QUEUE_START:-400}
mkdir -p "$RUN"

COMMON="--dht.experiment_prefix $PREFIX --optimizer.target_batch_size $TARGET \
  --averager.averaging_expiration $WINDOW --averager.averaging_timeout 180 \
  --training.learning_rate 0.15 --training.warmup_steps 200 \
  --training.total_steps 2500 \
  --training.queue_length 3840 --training.queue_start_step $QUEUE_START"

log() { echo "[orc] $(date +%T) $*" | tee -a "$RUN/orchestrator.log"; }

log "coordinator up"
JAX_PLATFORMS=cpu python -m dedloc_tpu.roles.coordinator \
  --dht.experiment_prefix "$PREFIX" --dht.listen_port "$PORT" \
  --coordinator.refresh_period 20 --coordinator.upload_interval 0 \
  --coordinator.metrics_log_path "$RUN/coordinator_metrics.jsonl" \
  > "$RUN/coordinator.log" 2>&1 &
COORD=$!
sleep 8

log "tpu swav peer up (ResNet-50 multicrop, queue from step 400)"
python -m dedloc_tpu.roles.swav $COMMON \
  --dht.initial_peers 127.0.0.1:"$PORT" \
  --averager.listen_port "$TPU_AVG_PORT" \
  --training.image_folder "$CORPUS/swav_images" \
  --training.per_device_batch_size 16 \
  --training.save_steps "$SAVE_STEPS" \
  --training.output_dir "$RUN/outputs" --training.seed 0 \
  > "$RUN/swav_tpu.log" 2>&1 &
TPU=$!
sleep 10

log "aux up (template self-bootstraps from the TPU peer's shared state)"
JAX_PLATFORMS=cpu nice -n 19 python -m dedloc_tpu.roles.aux \
  --dht.experiment_prefix "$PREFIX" --dht.initial_peers 127.0.0.1:"$PORT" \
  --optimizer.target_batch_size "$TARGET" \
  --averager.averaging_expiration "$WINDOW" --averager.averaging_timeout 180 \
  > "$RUN/aux.log" 2>&1 &
AUX=$!
sleep 20

cpu_volunteer() {
  # slow vision volunteer: same ResNet-50 param schema, small batch
  JAX_PLATFORMS=cpu nice -n 19 python -m dedloc_tpu.roles.swav $COMMON \
    --dht.initial_peers 127.0.0.1:"$PORT" \
    --training.image_folder "$CORPUS/swav_images" \
    --training.per_device_batch_size 2 \
    --training.save_steps 0 \
    --training.output_dir "$RUN/out_vol" --training.seed 1 \
    > "$RUN/swav_vol.log" 2>&1 &
  echo $!
}
log "cpu swav volunteer up"
VOL=$(cpu_volunteer)

sleep "$CHURN"
log "CHURN: SIGKILL swav volunteer (pid $VOL)"
kill -9 "$VOL" 2>/dev/null
sleep "$REJOIN"
log "CHURN: swav volunteer rejoins"
VOL=$(cpu_volunteer)

ELAPSED=$((CHURN + REJOIN))
sleep $((TOTAL - ELAPSED))
log "shutting down"
kill "$TPU" "$VOL" "$AUX" 2>/dev/null
sleep 25
kill -9 "$TPU" "$VOL" "$AUX" 2>/dev/null
kill "$COORD" 2>/dev/null
log "done"
