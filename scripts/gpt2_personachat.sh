#!/usr/bin/env bash
# GPT-2 / PersonaChat federated fine-tune at the reference defaults
# (gpt2_train.py:256 + utils.py:183-199: lr_scale 4e-2, local batch 8,
# 2 candidates, max_history 2, FetchSGD sketch compression). Place the
# personachat archive under $DATASET_DIR and the GPT-2 vocab +
# pytorch_model.bin under $MODEL_CHECKPOINT (zero-egress environment —
# nothing downloads). --num_cols 524288 is the lane-aligned twin of
# the reference's 500000 default: same compression ratio within 5%,
# and it engages the fused Pallas sketch kernels.
# --approx_topk is the perf choice at GPT-2 scale (74 vs 105 ms/round);
# drop it for the exact reference-parity selection — since round 3 the
# exact path costs ~40% more instead of 7x (threshold select).
set -euo pipefail

DATASET_DIR=${DATASET_DIR:-./data/personachat}
MODEL_CHECKPOINT=${MODEL_CHECKPOINT:-./data/gpt2}

python -m commefficient_tpu.train.gpt2_train \
    --dataset_name PERSONA \
    --dataset_dir "$DATASET_DIR" \
    --model_checkpoint "$MODEL_CHECKPOINT" \
    --mode sketch \
    --error_type virtual \
    --local_momentum 0 \
    --virtual_momentum 0.9 \
    --num_workers 4 \
    --local_batch_size 8 \
    --valid_batch_size 8 \
    --num_candidates 2 \
    --max_history 2 \
    --num_epochs 3 \
    --lr_scale 4e-2 \
    --k 50000 \
    --num_rows 5 \
    --num_cols 524288 \
    --bf16 \
    --approx_topk \
    "$@"
