#!/bin/bash
# Capture an xprof/Perfetto trace of the headline ResNet-50 train step on
# the real chip and record the bench JSON alongside it.  The committed
# .xplane.pb under bench_artifacts/ is the evidence behind the HBM-bound
# roofline claim in bench.py's docstring — reproducible with:
#
#   bash scripts/capture_profile.sh [out_dir]
#
# View with xprof/TensorBoard's profile plugin or Perfetto.
set -e
cd "$(dirname "$0")/.."
OUT=${1:-bench_artifacts/resnet50_xprof}
KFT_BENCH_PROFILE="$OUT" KFT_BENCH_BATCH=128 KFT_BENCH_STEPS=20 \
  python bench.py | tee "$OUT.bench.json"
echo "profile + bench line written under $OUT"
