#!/usr/bin/env bash
# Tier-1 verify: the one command that must stay green (see ROADMAP.md).
# This is exactly what CI (.github/workflows/ci.yml) runs on every push
# and pull request:
#
#   1. repo hygiene — no tracked bytecode (this regressed twice before
#      the gate existed);
#   2. the full pytest suite (collection regressions — import errors,
#      missing optional deps — show up here before anything else does);
#   3. the eight smoke benches via `benchmarks/run.py --smoke`
#      (columnar / index / residency / ingest / fuzzy / feeds / serve /
#      mesh), whose hard assertions catch: a row-vs-columnar divergence, an
#      index or fuzzy plan silently falling back to the row engine, a
#      candidate read regressing onto a python walk (the CSR postings
#      must beat the legacy secondary-LSM walk), a kernel retrace on
#      repeated queries, a warm index chain shipping host->device bytes
#      (the device buffer pool must keep operands resident), an ingest
#      pipeline divergence, a torn read / lost acknowledged record
#      under concurrent mixed ingest+query serving, or the SPMD
#      partition mesh diverging from (or losing to) the partition loop;
#   4. the structured bench report (`--json bench_smoke.json`) parses,
#      carries schema_version 1, contains rows from every smoke module,
#      the serve rows report nonzero sustained ingest, a p99 query
#      latency and a zero deadline-miss SLO ledger, and the residency
#      rows show warm queries uploading zero bytes at >= 3x the cold
#      latency — CI uploads the file as a run artifact;
#   5. the bench-history regression gate (`benchmarks/history.py
#      --check`) compares the fresh report row-by-row against the
#      committed `benchmarks/baseline.json` tolerance bands and fails on
#      any regression beyond band or drifted correctness invariant —
#      the delta table lands in bench_delta.json (also a CI artifact).
#      After a PR that legitimately moves the numbers, regenerate with
#      `python -m benchmarks.history --update` and commit the diff.
set -euo pipefail
cd "$(dirname "$0")/.."

# Repo hygiene: committed __pycache__/bytecode has regressed twice —
# fail fast if any tracked path matches.
if git ls-files | grep -E '(^|/)__pycache__(/|$)|\.pyc$' >/dev/null; then
    echo "verify: tracked bytecode files found:" >&2
    git ls-files | grep -E '(^|/)__pycache__(/|$)|\.pyc$' >&2
    exit 1
fi

# Fixed seed for the whole run: the row-vs-columnar differential harness
# (tests/test_differential.py, collected below) seeds per test name via
# the hypothesis shim (real hypothesis runs derandomized); exporting
# PYTHONHASHSEED pins the remaining hash-order dependence.
export PYTHONHASHSEED=0
PYTHONPATH=src${PYTHONPATH:+:$PYTHONPATH} python -m pytest -x -q "$@"

# Smoke-bench matrix: one invocation, one exit code (see run.py --smoke),
# plus a structured JSON report CI keeps as an artifact.  The mesh bench
# runs in-process on four devices, which the CPU backend only offers
# when XLA_FLAGS is set before Python starts.
XLA_FLAGS=--xla_force_host_platform_device_count=4 \
PYTHONPATH=src${PYTHONPATH:+:$PYTHONPATH} \
    python -m benchmarks.run --smoke --json bench_smoke.json

# The report must parse, be schema-stable, and cover every smoke
# module — a bench that crashed or was silently skipped fails here.
PYTHONPATH=src${PYTHONPATH:+:$PYTHONPATH} python - <<'EOF'
import json

report = json.load(open("bench_smoke.json"))
assert report["schema_version"] == 1, report["schema_version"]
assert report["smoke"] is True
assert not report["failures"], report["failures"]
from benchmarks.run import SMOKE_MODULES
ran = {row["module"] for row in report["benches"].values()}
missing = set(SMOKE_MODULES) - ran
assert not missing, f"smoke benches missing from report: {sorted(missing)}"
assert report["metrics"], "obs metric snapshot is empty"
# Concurrent-serving rows must carry real numbers: sustained ingest,
# measured tail latency, and a clean consistency ledger.
serve_rows = [r for r in report["benches"].values()
              if r["module"] == "serve"]
assert serve_rows, "no serve bench rows in report"
for row in serve_rows:
    assert row["ingest_rate"] > 0, f"zero sustained ingest: {row}"
    assert row["query_p99_ms"] is not None, f"missing p99: {row}"
    assert row["torn_reads"] == 0 and row["lost_acked"] == 0, row
    # deadline SLO ledger must be clean at smoke load
    assert row["slo_missed"] == 0, f"deadline misses at smoke load: {row}"
    assert row["slo_rejected_deadline"] == 0, f"deadline rejections: {row}"
    assert row["queue_wait_p99_ms"] is not None, f"missing queue wait: {row}"
# Residency rows must prove upload-once semantics: warm repeats of a
# Figure-6 chain ship nothing host->device, never retrace, and beat
# the cold (trace + upload) execution by >= 3x.
res_rows = [r for r in report["benches"].values()
            if r["module"] == "residency"]
assert res_rows, "no residency bench rows in report"
for row in res_rows:
    assert row["h2d_cold"] > 0, f"cold run uploaded nothing: {row}"
    assert row["h2d_warm"] == 0, f"warm query shipped bytes: {row}"
    assert row["retraces_warm"] == 0, f"warm query retraced: {row}"
    assert row["speedup"] >= 3.0, f"warm speedup under 3x: {row}"
# Mesh rows must prove the SPMD refactor held: one shard_map dispatch
# answered for all partitions, bit-identically, from resident shards.
mesh_rows = [r for r in report["benches"].values()
             if r["module"] == "mesh"]
assert mesh_rows, "no mesh bench rows in report"
for row in mesh_rows:
    assert row["spmd_dispatches"] >= 1, f"no SPMD dispatch: {row}"
    assert row["h2d_warm"] == 0, f"warm mesh query shipped bytes: {row}"
    assert row["retraces_warm"] == 0, f"warm mesh query retraced: {row}"
print(f"verify: bench_smoke.json ok "
      f"({len(report['benches'])} benches, {len(report['metrics'])} metrics)")
EOF

# Bench-history regression gate: the fresh smoke numbers must stay
# within the committed baseline's per-row tolerance bands.
PYTHONPATH=src${PYTHONPATH:+:$PYTHONPATH} \
    python -m benchmarks.history --check \
        --baseline benchmarks/baseline.json \
        --fresh bench_smoke.json --report bench_delta.json
