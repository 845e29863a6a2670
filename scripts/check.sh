#!/usr/bin/env bash
# Repo gate: lint (ruff), kf-verify static analysis, chaos smoke, tier-1 tests.
#
#   scripts/check.sh            # run everything
#   scripts/check.sh --fast     # skip the chaos smoke + tier-1 pytest run
#
# Exits non-zero on the first failing stage.
set -euo pipefail
cd "$(dirname "$0")/.."

fast=0
[ "${1:-}" = "--fast" ] && fast=1

echo "== ruff =="
# unconditional gate: a missing linter must fail loudly, not silently
# wave the tree through (CI installs ruff; see .github/workflows/ci.yaml)
if command -v ruff >/dev/null 2>&1; then
    ruff check kungfu_tpu tests examples scripts
elif python -c "import ruff" >/dev/null 2>&1; then
    python -m ruff check kungfu_tpu tests examples scripts
else
    echo "ERROR: ruff is not installed — the lint gate cannot run" >&2
    echo "       (pip install ruff; config lives in pyproject.toml)" >&2
    exit 1
fi

echo "== kf-verify: schedules + hostlint + env audit (must be clean) =="
JAX_PLATFORMS=cpu python -m kungfu_tpu.analysis --schedules --hostlint --env

echo "== kf-verify: jaxpr corpus (must be clean) =="
JAX_PLATFORMS=cpu python -m kungfu_tpu.analysis

echo "== kf-verify: seeded-bad programs + schedules (must fail) =="
if JAX_PLATFORMS=cpu python -m kungfu_tpu.analysis \
        --module kungfu_tpu.testing.bad_programs >/dev/null 2>&1; then
    echo "ERROR: seeded-bad programs analyzed clean — the rules lost teeth" >&2
    exit 1
fi
echo "ok (exit non-zero as expected)"

echo "== kf-verify: seeded-bad host code (must fail) =="
if JAX_PLATFORMS=cpu python -m kungfu_tpu.analysis \
        --hostlint kungfu_tpu/testing/bad_host.py >/dev/null 2>&1; then
    echo "ERROR: seeded-bad host code linted clean — hostlint lost teeth" >&2
    exit 1
fi
echo "ok (exit non-zero as expected)"

if [ "$fast" = "1" ]; then
    echo "== chaos smoke + tier-1 pytest skipped (--fast) =="
    exit 0
fi

echo "== planner smoke: enumerate -> lint -> cost -> install (2-rank CPU) =="
# the full plan-compiler pipeline must run end to end: every enumerated
# candidate passes kf-lint, the seeded illegal one is rejected + journaled,
# the measured winner installs (strategy + wire dtype change on the live
# Session), and the plan cache persists — the SECOND run must report a
# cache hit and skip re-measurement (docs/planner.md)
plan_cache_dir=$(mktemp -d)
JAX_PLATFORMS=cpu python -m kungfu_tpu.planner --smoke --np 2 \
    --cache "$plan_cache_dir/plan_cache.json"
JAX_PLATFORMS=cpu python -m kungfu_tpu.planner --smoke --np 2 \
    --cache "$plan_cache_dir/plan_cache.json" --expect-cache-hit
rm -rf "$plan_cache_dir"

echo "== tuner smoke: enumerate -> footprint gate -> runoff -> install (CPU) =="
# the compute-autotuner pipeline must run end to end: the footprint gate
# rejects + journals a seeded oversized tiling, the measured runoff keeps
# the hand-tuned default as a control (the winner never loses to it),
# apply() lands the winner on a TransformerConfig, tuned-vs-default
# forward parity is bit-identical, and the prior cache persists — the
# SECOND run must be a pure cache hit and skip the runoff (docs/tuning.md)
tuner_cache_dir=$(mktemp -d)
JAX_PLATFORMS=cpu python -m kungfu_tpu.tuner --smoke \
    --cache "$tuner_cache_dir/prior_cache.json"
JAX_PLATFORMS=cpu python -m kungfu_tpu.tuner --smoke \
    --cache "$tuner_cache_dir/prior_cache.json" --expect-cache-hit
rm -rf "$tuner_cache_dir"

echo "== pallas parity: interpret-mode ring kernels vs XLA lowerings =="
# the hand-scheduled ring RS/AG + fused-codec kernels must be bit-exact /
# within computed quant tolerance of the lax.* paths, bucketed grad-sync
# identical to unbucketed, and every registered pallas plan kf-lint-clean
JAX_PLATFORMS=cpu python -m pytest tests/unit/test_pallas_collectives.py \
    -q -m 'not slow' -p no:cacheprovider

echo "== pallas smoke: set_strategy(pallas_ring) + off-TPU fallback (2-rank CPU) =="
# forcing PALLAS_RING through Session.set_strategy must (1) engage the lax
# fallback cleanly off-TPU with correct sums and an honest impl=xla stamp,
# (2) run the real kernel bodies under KFT_PALLAS=interpret bit-identically,
# (3) keep the fused int8 path inside its quantization tolerance
JAX_PLATFORMS=cpu python -m kungfu_tpu.ops.pallas_collectives --smoke --np 2

echo "== fused-matmul smoke: interpret kernels + clean fallback (2-rank CPU) =="
# the fused computation-collective entry points (all-gather-matmul,
# matmul-reduce-scatter, the dma gather/scatter pair, the ring-shift hop)
# must (1) produce the exact lax results through the clean fallback with
# the gate off, (2) run the real kernel bodies bit-identically under
# KFT_PALLAS=interpret, (3) flow gradients through the custom VJPs
JAX_PLATFORMS=cpu python -m kungfu_tpu.ops.fused_matmul --smoke --np 2

echo "== chaos smoke: scripted crash+heal drill (CPU, buddy-RAM rung) =="
# --expect-rung buddy: the heal must resync from the peer-redundant
# in-memory tier (recovery_rung=buddy journaled, zero disk restores)
JAX_PLATFORMS=cpu python -m kungfu_tpu.chaos \
    --np 2 --plan "crash@step=5:rank=1" --total-samples 512 --timeout 180 \
    --expect-rung buddy

echo "== checkpoint integrity: corrupt-step drill (CPU) =="
# post-finalize byte flips must demote the corrupted step (journaled) and
# the restart must land on the prior verified step, exit 0 end to end
JAX_PLATFORMS=cpu python -m kungfu_tpu.chaos --ckpt-drill corrupt --timeout 240

echo "== checkpoint integrity: crash-in-save drill (CPU) =="
# a primary killed between array commit and manifest rename leaves a torn
# step; the restart must demote it and resume from the verified one
JAX_PLATFORMS=cpu python -m kungfu_tpu.chaos --ckpt-drill crash_in_save --timeout 240

echo "== serving smoke: rank kill + buddy rejoin + autoscale drill (CPU) =="
# a 2-rank serving fleet survives a scripted crash_serve kill mid-stream:
# zero dropped requests (the router re-queues the victim's in-flight work),
# the victim rejoins from a live peer's weights (journal rank_rejoined with
# recovery_rung=buddy, sub-second), and queue-depth-driven scale-down then
# scale-up both commit through the config server (docs/serving.md)
JAX_PLATFORMS=cpu python -m kungfu_tpu.chaos --serve-drill --timeout 300

echo "== serving v2: prefill-tier rank kill drill (CPU, disaggregated) =="
# the disaggregated fleet (1 prefill + 2 decode) survives a prefill-rank
# crash mid-burst: the router's dispatch dies and re-queues (zero drops,
# p99 bounded), the victim respawns and journals a tier-stamped
# rank_rejoined (docs/serving.md "Disaggregated pools")
JAX_PLATFORMS=cpu python -m kungfu_tpu.chaos --serve-drill --tier prefill --timeout 300

echo "== serving v2: decode-tier rank kill drill (CPU, disaggregated) =="
# same fleet, decode-rank crash mid-stream: the prefill proxy's 502
# surfaces as a failed dispatch, warm progress recovers from the DEAD
# decode rank's ring buddy, every request completes
JAX_PLATFORMS=cpu python -m kungfu_tpu.chaos --serve-drill --tier decode --timeout 300

echo "== trace drill: stitched cross-process request traces + tail attribution (CPU) =="
# the decode-tier serve drill plus distributed tracing: every completed
# request must stitch into a multi-process trace on the fleet /requests
# endpoint (>= 2 process lanes, zero orphan spans; failover victims carry
# the requeue + warm_graft spans), and an induced slow_serve@phase=kv_ship
# window must journal a request-latency slo_breach naming kv_ship as the
# dominant phase (docs/observability.md "Request tracing")
JAX_PLATFORMS=cpu python -m kungfu_tpu.chaos --trace-drill --timeout 300

echo "== fairness drill: multi-tenant QoS under an adversarial mix (CPU) =="
# a tenanted fleet (sensitive/batch/bursty classes) under a burst@ traffic
# shape plus a decode delay: the bursty tenant's overrun must be journaled
# as tenant_rate_limited 429s, the sensitive class must preempt a batch
# slot (slot_preempted -> warm preempted_readmitted, byte-identical greedy
# replay), the sensitive p99 must stay inside its tenant= SLO rule, and
# zero admitted requests drop (docs/serving.md "Multi-tenancy & QoS")
JAX_PLATFORMS=cpu python -m kungfu_tpu.chaos --fairness-drill --timeout 300

echo "== straggler drill: slow rank fingered, not killed (CPU) =="
# a slow@-injected rank (per-step sleep > heartbeat timeout) must be
# flagged by the fleet /stragglers detector (journal straggler_suspected
# with the right rank, zero false positives on clean ranks) BEFORE the
# stall deadline, while the healer's graded judgment journals worker_slow
# instead of killing it — the job finishes at full size
JAX_PLATFORMS=cpu python -m kungfu_tpu.chaos --straggler-drill --timeout 240

echo "== coordinator drill: replicated control plane through leader kill + partition (CPU) =="
# CAS-storm traffic (healer + two autoscalers + reconvene nudges + KV
# heartbeats, all through the KFT_CONFIG_URLS failover client) against a
# 3-replica config ensemble, through a leader SIGKILL and a leader
# SIGSTOP partition: zero dropped requests, zero lost/double-applied
# conditional PUTs, bounded unavailability, leader_elected journaled,
# every replica converged on one committed log
# (docs/fault_tolerance.md "Replicated control plane")
JAX_PLATFORMS=cpu python -m kungfu_tpu.chaos --coordinator-drill --timeout 300

echo "== pod drill smoke: 4 netns hosts, shaped links, kill_host + partition =="
# the simulated-pod harness (docs/fault_tolerance.md "network failure
# model"): schedule resize, then a whole-host SIGKILL that must heal as
# EXACTLY ONE shrink CAS (all the host's ranks at once, recovery at rung
# buddy), then a partition that must be suspected — never shrunk — and
# rejoined at unchanged membership via reconvene bumps once it heals.
# Auto-SKIPs (exit 0) without root/netns, same contract as the netns drills.
python scripts/pod_drill.py --smoke --timeout 420

echo "== SLO drill: chaos slow@ drives a sustained breach that clears (CPU) =="
# 2-rank fleet under -telemetry -slo-exit-code with a tight step-latency
# SLO: the slow window must journal a sustained slo_breach (/slo shows the
# rule active, /history serves the windowed p99 series that drove it), the
# breach must clear after the window passes (slo_cleared), and the
# otherwise-clean launcher must exit with the SLO exit code
# (docs/observability.md)
JAX_PLATFORMS=cpu python -m kungfu_tpu.monitor --slo-drill --timeout 240

echo "== compile drill: recompile storm trips the shipped SLO rule; clean serving holds its budget (CPU) =="
# program observatory end to end: a 1-rank fleet running seeded shape
# churn must journal program_compiled per signature + recompile_storm,
# surface the registry on the fleet /programs endpoint, and trip the
# SHIPPED rate:recompile_storm rule under -slo-exit-code; then a clean
# in-process serving engine under mixed prefill/decode traffic must end
# with exactly its declared signatures (decode 1) and a compile count
# that stays constant when the traffic repeats
# (docs/observability.md "Program observatory")
JAX_PLATFORMS=cpu python -m kungfu_tpu.monitor --compile-drill --timeout 240

echo "== telemetry smoke: fleet aggregation + merged timeline (CPU) =="
# 2-process run under -telemetry: fleet /metrics must merge both ranks
# with consistent counter sums, /timeline must parse as valid Chrome trace
# JSON with per-rank lanes, and the crash+heal plan must land in the
# journal + a decomposed heal span (docs/observability.md)
JAX_PLATFORMS=cpu python -m kungfu_tpu.monitor --smoke --np 2 --timeout 180

echo "== tier-1 pytest =="
JAX_PLATFORMS=cpu python -m pytest tests/ -q -m 'not slow' \
    --continue-on-collection-errors -p no:cacheprovider
