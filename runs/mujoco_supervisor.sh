#!/bin/bash
# Supervised REAL-MuJoCo training legs — the halfcheetah_tpu_r2 recipe
# (8-actor async pool, CPU-jitted acting, K=32 fused dispatch, async PER
# write-back, exit-75 RSS self-preemption) pointed at any gymnasium env.
# Single critic by default: the round-4 Hopper comparison showed clipped
# double-Q's pessimism suppresses the optimistic Q that discovers hop/
# gait cycles on real contacts (twin best 1,030 vs single 3,558 —
# runs/hopper_mujoco_tpu_twin vs runs/hopper_mujoco_tpu). Pass
# --twin-critic via EXTRA args
# for the ablation arm.
# Usage: bash runs/mujoco_supervisor.sh ENV DIR [TOTAL_STEPS] [EXTRA...]
#   e.g. bash runs/mujoco_supervisor.sh Hopper-v5 runs/hopper_mujoco_tpu
ENV_ID=${1:?usage: mujoco_supervisor.sh ENV DIR [TOTAL] [extra flags...]}
DIR=${2:?usage: mujoco_supervisor.sh ENV DIR [TOTAL] [extra flags...]}
TOTAL=${3:-2000000}
shift 3 2>/dev/null || shift 2
while :; do
  STEP=$(ls "$DIR/checkpoints" 2>/dev/null | grep -E '^[0-9]+$' | sort -n | tail -1)
  STEP=${STEP:-0}
  REM=$((TOTAL - STEP))
  if [ "$REM" -le 0 ]; then echo "supervisor: done at step $STEP"; break; fi
  echo "supervisor: leg from step $STEP, $REM to go"
  python train.py --env "$ENV_ID" --num-envs 8 --async-collect \
    --async-writeback --steps-per-dispatch 32 --n-step 3 \
    --noise-decay-steps 1000000 --noise-scale-final 0.15 \
    --total-steps "$REM" --eval-interval 10000 \
    --eval-episodes 5 --checkpoint-interval 100000 --snapshot-replay \
    --resume --max-rss-gb 80 --log-dir "$DIR" "$@"
  RC=$?
  # 75 = watchdog preemption (checkpointed; go again); 0 = leg budget done
  if [ "$RC" -ne 75 ] && [ "$RC" -ne 0 ]; then
    echo "supervisor: leg failed rc=$RC"; exit "$RC"
  fi
done
