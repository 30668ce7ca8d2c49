#!/usr/bin/env bash
# CI gate: tier-1 tests under BOTH device topologies, example smoke runs,
# and the quick benchmarks that back the committed artifacts
# (BENCH_lookup.json / BENCH_dist.json / BENCH_scale.json).
#
#   bash scripts/ci.sh            # full gate (~30 min on CPU)
#   bash scripts/ci.sh --fast     # tests + examples only
set -euo pipefail
cd "$(dirname "$0")/.."
export PYTHONPATH=src${PYTHONPATH:+:$PYTHONPATH}

# Public-API drift gate (ISSUE 5): fails when the exported surface no
# longer matches the committed api_surface.txt — API changes must be
# declared (regenerate the file), never accidental.
echo "== public API surface =="
python scripts/api_surface.py --check

# Fast tracing-count gate (seconds): fails if appends within a
# capacity class retrace any fused read entry point — free functions AND
# the IndexedFrame facade (ISSUE 4 + 5 acceptance; DESIGN.md §4, §11).
# Run under both topologies so the shard_map backend's gate executes
# even on single-device CI.
echo "== trace gate (single device) =="
python scripts/trace_gate.py
echo "== trace gate (forced 8-device host mesh) =="
XLA_FLAGS="${XLA_FLAGS:-} --xla_force_host_platform_device_count=8" \
  python scripts/trace_gate.py

# Fast fault-injection smoke (seconds): a seeded shard kill through the
# supervised frame must heal automatically — bit-identical answers vs a
# never-failed twin, ONE trace of the fused read site, replay bounded by
# the checkpoint suffix (ISSUE 6 acceptance; DESIGN.md §12).  Both
# topologies, so the recovery state machine runs on shard_map too.
echo "== fault smoke (single device) =="
python scripts/fault_smoke.py
echo "== fault smoke (forced 8-device host mesh) =="
XLA_FLAGS="${XLA_FLAGS:-} --xla_force_host_platform_device_count=8" \
  python scripts/fault_smoke.py

# Fast serving smoke (seconds): a short mixed read/write run through
# the continuous-batching QueryEngine — p99 finite, every answer
# bit-identical to an unbatched twin replaying the engine's write_log,
# zero retraces after warmup, one version bump per flush (ISSUE 8
# acceptance; DESIGN.md §14).  Both topologies, plus an explicit run of
# the serving suite (it also rides the full tier-1 passes below — the
# forced-8 pass runs the in-process shard_map serving tests).
echo "== serve smoke (single device) =="
python scripts/serve_smoke.py
echo "== serve smoke (forced 8-device host mesh) =="
XLA_FLAGS="${XLA_FLAGS:-} --xla_force_host_platform_device_count=8" \
  python scripts/serve_smoke.py
echo "== serving suite (single device) =="
python -m pytest -q tests/test_serving.py tests/test_query_engine.py
echo "== serving suite (forced 8-device host mesh) =="
XLA_FLAGS="${XLA_FLAGS:-} --xla_force_host_platform_device_count=8" \
  python -m pytest -q tests/test_serving.py tests/test_query_engine.py

echo "== tier-1 pytest (single device) =="
python -m pytest -q

# Second pass on a forced 8-device host mesh: the shard_map backend's
# parity suite (tests/test_mesh_parity.py) runs its full in-process
# matrix here instead of skipping to the subprocess fallback.
echo "== tier-1 pytest (forced 8-device host mesh) =="
XLA_FLAGS="${XLA_FLAGS:-} --xla_force_host_platform_device_count=8" \
  python -m pytest -q

echo "== example smoke =="
python scripts/smoke_examples.py

if [[ "${1:-}" != "--fast" ]]; then
  echo "== quick benchmarks =="
  python -m benchmarks.run --only lookup_path --out /tmp/ci_bench_lookup.json
  python -m benchmarks.run --only fault_tolerance --out /tmp/ci_bench_dist.json
  XLA_FLAGS="${XLA_FLAGS:-} --xla_force_host_platform_device_count=4" \
    python -m benchmarks.run --only scalability --out /tmp/ci_bench_scale.json
fi

echo "CI gate OK"
