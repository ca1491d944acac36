#!/bin/bash
# Round-5 heterogeneous collaborative run with the HARDENED transport live:
# a TPU trainer + 2 client-mode CPU volunteers (each registered with k=2
# circuit relays, upgrading peer<->peer paths via NAT punch / connection
# reversal) + an aux bandwidth donor + the coordinator running the
# AllowlistAuthServer, so every matchmaking envelope is gated. This is the
# single-host analogue of the reference's REAL deployment shape
# (sahajbert/huggingface_auth.py gated volunteers + p2p/NAT-traversal.md
# private nodes), at the solo recipe's scale (target_batch_size 512, LAMB
# 6e-4) so the loss curve is comparable to artifacts/r4/solo_train_log.jsonl
# at matched samples.
#
# Modes:
#   MODE=probe    — short fixed-DURATION run, no churn: used to sweep
#                   averaging_expiration (straggler window) and measure
#                   volunteer round-participation vs TPU cadence
#                   (tools/participation_summary.py eats the logs).
#   MODE=converge — the long run: two SIGKILL/rejoin churn events, runs
#                   until TOTAL seconds elapsed.
#
# Usage:
#   CORPUS=/root/corpus RUN=/root/corpus/r5_probe_w30 WINDOW=30 \
#     MODE=probe DURATION=420 bash tools/hetero_converge.sh
#   CORPUS=/root/corpus RUN=/root/corpus/r5_converge WINDOW=30 \
#     MODE=converge TOTAL=23400 CHURN1=5400 REJOIN1=600 CHURN2=14400 \
#     REJOIN2=600 bash tools/hetero_converge.sh
set -u
# location-independent: the package is not pip-installed, so put this
# script's checkout on the path
REPO="$(cd "$(dirname "${BASH_SOURCE[0]}")/.." && pwd)"
export PYTHONPATH="$REPO${PYTHONPATH:+:$PYTHONPATH}"
# persistent XLA compile cache: every role keeps it in <checkout>/.jax_cache
# unless JAX_COMPILATION_CACHE_DIR says otherwise
# (dedloc_tpu/utils/backend.py) — the CPU volunteers' ALBERT-large compile
# takes minutes on one contended core, and every later peer (and churn
# rejoin) then starts stepping in seconds
CORPUS=${CORPUS:-/root/corpus}
RUN=${RUN:-$CORPUS/r5_run}
PREFIX=${PREFIX:-hetero5}
PORT=${PORT:-41000}        # coordinator (DHT bootstrap + auth server)
# the AVERAGER'S RPC server is the circuit relay (dht/protocol.py
# RelayService attaches to listening averagers) — pin those ports and
# point the volunteers' --dht.relay at them
TPU_AVG_PORT=${TPU_AVG_PORT:-41011}  # TPU trainer averager = relay 1
AUX_AVG_PORT=${AUX_AVG_PORT:-41013}  # aux donor averager = relay 2
WINDOW=${WINDOW:-30}
TARGET=${TARGET:-512}
LEAD=${LEAD:-0}
MODE=${MODE:-probe}
DURATION=${DURATION:-420}
TOTAL=${TOTAL:-23400}
CHURN1=${CHURN1:-5400}
REJOIN1=${REJOIN1:-600}
CHURN2=${CHURN2:-14400}
REJOIN2=${REJOIN2:-600}
SAVE_STEPS=${SAVE_STEPS:-250}
TOTAL_STEPS=${TOTAL_STEPS:-4000}
RELAYS="127.0.0.1:$TPU_AVG_PORT,127.0.0.1:$AUX_AVG_PORT"
# gated run: coordinator holds the allowlist, every peer presents creds
ALLOW="tpu:r5-tpu-pw,vol1:r5-vol1-pw,vol2:r5-vol2-pw,aux:r5-aux-pw"
mkdir -p "$RUN"

COMMON="--dht.experiment_prefix $PREFIX --optimizer.target_batch_size $TARGET \
  --optimizer.batch_size_lead $LEAD \
  --averager.averaging_expiration $WINDOW --averager.averaging_timeout 180 \
  --training.learning_rate 0.0006 --training.warmup_steps 250 \
  --training.total_steps $TOTAL_STEPS"

log() { echo "[orc] $(date +%T) $*" | tee -a "$RUN/orchestrator.log"; }

log "coordinator up (auth-gated: allowlist of 4)"
JAX_PLATFORMS=cpu python -m dedloc_tpu.roles.coordinator \
  --dht.experiment_prefix "$PREFIX" --dht.listen_port "$PORT" \
  --coordinator.auth_allowlist "$ALLOW" \
  --coordinator.refresh_period 20 --coordinator.upload_interval 0 \
  --coordinator.metrics_log_path "$RUN/coordinator_metrics.jsonl" \
  > "$RUN/coordinator.log" 2>&1 &
COORD=$!
sleep 8

log "tpu trainer up (solo recipe: flash + fused_ln, 12x4, LAMB 6e-4 w250)"
python -m dedloc_tpu.roles.trainer $COMMON \
  --dht.initial_peers 127.0.0.1:"$PORT" \
  --averager.listen_port "$TPU_AVG_PORT" \
  --auth.username tpu --auth.credential r5-tpu-pw \
  --training.dataset_path "$CORPUS/tokenized" \
  --training.per_device_batch_size 12 \
  --training.gradient_accumulation_steps 4 \
  --training.remat_policy fused_ln --training.attention_impl flash \
  --training.train_log_path "$RUN/train_log_tpu.jsonl" \
  --training.output_dir "$RUN/outputs" --training.save_steps "$SAVE_STEPS" \
  --training.seed 0 \
  > "$RUN/trainer_tpu.log" 2>&1 &
TPU=$!
sleep 10

log "aux up (public listener + relay 2)"
JAX_PLATFORMS=cpu nice -n 19 python -m dedloc_tpu.roles.aux \
  --dht.experiment_prefix "$PREFIX" --dht.initial_peers 127.0.0.1:"$PORT" \
  --averager.listen_port "$AUX_AVG_PORT" \
  --auth.username aux --auth.credential r5-aux-pw \
  --training.model_size large --training.seq_length 128 \
  --optimizer.target_batch_size "$TARGET" \
  --averager.averaging_expiration "$WINDOW" --averager.averaging_timeout 180 \
  > "$RUN/aux.log" 2>&1 &
AUX=$!
# let the two relay hosts (TPU trainer + aux) start listening before the
# client-mode volunteers try to register with them
sleep 35

cpu_volunteer() {
  # a private volunteer: outbound-only (client_mode), reachable through the
  # k=2 circuit relays; volunteer<->volunteer averaging spans upgrade via
  # NAT hole punch, volunteer<->public via connection reversal. Streams raw
  # text (on-the-fly tokenization) at seq 128, batch 1 — same param schema
  # as the TPU peer so gradients average.
  local i=$1
  JAX_PLATFORMS=cpu nice -n 19 python -m dedloc_tpu.roles.trainer $COMMON \
    --dht.initial_peers 127.0.0.1:"$PORT" \
    --dht.client_mode true --dht.relay "$RELAYS" \
    --auth.username "vol$i" --auth.credential "r5-vol$i-pw" \
    --training.streaming_files "$CORPUS/train.txt" \
    --training.tokenizer_path "$CORPUS/tokenizer.json" \
    --training.seq_length 128 \
    --training.per_device_batch_size 1 \
    --training.gradient_accumulation_steps 1 \
    --training.remat_policy nothing --training.attention_impl dense \
    --averager.bandwidth 100 \
    --training.train_log_path "$RUN/train_log_vol$i.jsonl" \
    --training.output_dir "$RUN/out_vol$i" --training.save_steps 0 \
    --training.seed "$i" \
    > "$RUN/trainer_vol$i.log" 2>&1 &
  echo $!
}
log "client-mode volunteers up (relays: $RELAYS)"
V1=$(cpu_volunteer 1)
V2=$(cpu_volunteer 2)

if [ "$MODE" = probe ]; then
  sleep "$DURATION"
  log "probe window=$WINDOW done"
else
  sleep "$CHURN1"
  log "CHURN 1: SIGKILL vol2 (pid $V2)"
  kill -9 "$V2" 2>/dev/null
  sleep "$REJOIN1"
  log "CHURN 1: vol2 rejoins (state pull over the hardened path)"
  V2=$(cpu_volunteer 2)
  ELAPSED=$((CHURN1 + REJOIN1))
  sleep $((CHURN2 - ELAPSED))
  log "CHURN 2: SIGKILL vol1 (pid $V1)"
  kill -9 "$V1" 2>/dev/null
  sleep "$REJOIN2"
  log "CHURN 2: vol1 rejoins"
  V1=$(cpu_volunteer 1)
  ELAPSED=$((CHURN2 + REJOIN2))
  sleep $((TOTAL - ELAPSED))
fi

log "shutting down"
kill "$TPU" "$V1" "$V2" "$AUX" 2>/dev/null
sleep 25
kill -9 "$TPU" "$V1" "$V2" "$AUX" 2>/dev/null
kill "$COORD" 2>/dev/null
log "done"
