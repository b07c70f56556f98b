#!/usr/bin/env bash
# Tier-1 gate: full unit suite + a fast serving-benchmark sanity run.
# Usage: scripts/tier1.sh
set -euo pipefail
cd "$(dirname "$0")/.."
export PYTHONPATH="src${PYTHONPATH:+:$PYTHONPATH}"

echo "== tier-1: pytest (fast: -m 'not slow') =="
python -m pytest -x -q -m "not slow"

echo "== tier-1: invariant lint (repro.analysis --check) =="
# static passes: clock-purity, lock-discipline, conformance, gauge-schema;
# fails only on findings not in the committed analysis-baseline.json
python -m repro.analysis --check > /dev/null

echo "== tier-1: serving benchmark smoke =="
python -m benchmarks.serving --smoke > /dev/null

echo "== tier-1: spec-built serving smoke =="
python -m repro.launch.serve --config examples/specs/smoke.json \
    --mode open --requests 20 > /dev/null

echo "== tier-1: elastic scaling smoke (static vs elastic, bursty) =="
# --check asserts: elastic SLO goodput/p99 >= static, outputs equivalent to
# lock-step with control disabled, scaling events replay deterministically
python -m benchmarks.elastic_scaling --smoke --check > /dev/null

echo "== tier-1: continuous-batching gen engine smoke =="
# --check asserts: engine outputs identical to lock-step ModelLLM and a
# TTFT p95 win under the bursty mixed-prompt-length workload
python -m benchmarks.gen_engine --smoke --check > /dev/null

echo "== tier-1: scenario golden-trace replay (deterministic sim) =="
# --check replays every registered scenario through the wall-clock-free
# simulator and asserts the (scaling events, knob timeline, quality-aware
# goodput) trace matches tests/golden/ bit-for-bit
python -m benchmarks.scenarios --check > /dev/null

echo "== tier-1: chaos recovery smoke (fault injection, deterministic) =="
# --check asserts: chaos scenarios are bit-deterministic and lossless
# (availability + error_rate == 1, replica kills lose zero requests),
# kill->respawn pairing, straggler retire, writer-stall spike + drain
python -m benchmarks.chaos --check > /dev/null

echo "== tier-1: sharded retrieval smoke (parity + flat-p99 scaling) =="
# --check asserts: n_shards=1 output-identical to JaxVectorDB, 4-shard
# recall parity, and sim-backed p99 within 1.3x single-shard while the
# corpus scales 8x (the shard_scale golden itself rides scenarios --check)
python -m benchmarks.sharded_retrieval --smoke --check > /dev/null

echo "== tier-1: fused retrieve gate (parity + roofline + latency) =="
# --check asserts: fused backend bit-exact vs the reference ladder on all
# index_type x quant configs under interpret AND xla modes (incl. after
# mutations/tombstones), fused HBM bytes strictly closer to the bandwidth
# bound, and a micro-batch latency win on the sq8/pq xla paths
python -m benchmarks.fused_retrieve --smoke --check > /dev/null

echo "== tier-1: trace export smoke (sim spans -> Chrome trace) =="
# deterministic sim trace written as Chrome trace_event JSON + JSONL,
# then structurally validated by the exporter CLI
python -m repro.launch.serve --scenario steady --scenario-sim \
    --scenario-scale 0.25 --trace-out /tmp/ragperf_tier1_trace.json > /dev/null
python -m repro.obs /tmp/ragperf_tier1_trace.json > /dev/null

echo "tier-1 OK"
