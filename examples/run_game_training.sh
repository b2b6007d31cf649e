#!/usr/bin/env bash
# Full GAME training + scoring workflow on synthetic recommender data
# (the analogue of the reference's examples/run_photon_ml_driver.sh).
#
#   CPU:   JAX_PLATFORMS=cpu bash examples/run_game_training.sh
#   chip:  chiprun -- bash examples/run_game_training.sh
#
# Runs from a clean checkout: nothing is installed, so the repo root goes on
# PYTHONPATH here. The three steps are separate processes run one after the
# other, so each has the chip to itself.
set -euo pipefail

ROOT=$(cd "$(dirname "${BASH_SOURCE[0]}")/.." && pwd)
export PYTHONPATH="$ROOT${PYTHONPATH:+:$PYTHONPATH}"
cd "$ROOT"

DATA=${DATA:-/tmp/photon-tpu-recsys}
OUT=${OUT:-/tmp/photon-tpu-out}

python examples/generate_recsys_data.py --output-dir "$DATA"

python -m photon_ml_tpu.cli.game_training_driver \
  --input-data-path "$DATA/train" \
  --validation-data-path "$DATA/val" \
  --root-output-dir "$OUT/train" \
  --task-type LINEAR_REGRESSION \
  --feature-shard-configurations "name=global,feature.bags=features,intercept=true" \
  --feature-shard-configurations "name=userShard,feature.bags=userFeatures,intercept=false" \
  --feature-shard-configurations "name=itemShard,feature.bags=itemFeatures,intercept=false" \
  --coordinate-configurations "name=fe,feature.shard=global,reg.weights=0.01|1" \
  --coordinate-configurations "name=per-user,feature.shard=userShard,random.effect.type=userId,reg.weights=1,optimizer=NEWTON" \
  --coordinate-configurations "name=per-item,feature.shard=itemShard,random.effect.type=itemId,reg.weights=1,optimizer=NEWTON" \
  --coordinate-configurations "name=mf,mf.row.effect.type=userId,mf.col.effect.type=itemId,mf.latent.factors=4,reg.weights=0.01" \
  --coordinate-descent-iterations 3 \
  --evaluators "RMSE,RMSE:queryId" \
  --checkpoint-dir "$OUT/ckpt"

python -m photon_ml_tpu.cli.game_scoring_driver \
  --input-data-path "$DATA/val" \
  --model-input-dir "$OUT/train/best" \
  --index-maps-dir "$OUT/train/index-maps" \
  --output-dir "$OUT/scores" \
  --evaluators RMSE \
  --feature-shard-configurations "name=global,feature.bags=features,intercept=true" \
  --feature-shard-configurations "name=userShard,feature.bags=userFeatures,intercept=false" \
  --feature-shard-configurations "name=itemShard,feature.bags=itemFeatures,intercept=false"

echo "training summary: $OUT/train/training-summary.json"
echo "scores:           $OUT/scores"
