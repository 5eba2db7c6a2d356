"""docs/METRICS.md is the metric-name registry — keep it honest.

Runs a compact workload across every metric-emitting subsystem (LSM
lifecycle + loop/mesh queries inline, feeds + serving via their smoke
benches), then asserts every metric name in ``obs.snapshot()`` appears
in the doc.  Parametrized name segments (per-kernel splits, feed names,
mesh shard ids, subscriber lags) are canonicalized to the placeholder
forms the doc's tables use (``kernel.<kernel>.dispatches``,
``feed.joint.<joint>.lag.<sub>``, ``mesh.shard<k>.h2d_bytes``, ...).

A new metric therefore fails this test until it is documented — the
registry cannot silently drift from the code again (it previously lived
in the ``obs/__init__`` docstring, where nothing checked it).

The workload runs in a fresh interpreter: the registry is process-wide,
so names that other test files register in the same worker would
otherwise leak into the check.
"""

import json
import os
import pathlib
import re
import subprocess
import sys

import pytest

from repro import obs
from repro.columnar import plancache as PC
from repro.core import algebra as A
from repro.core.lsm import TieredMergePolicy
from repro.storage.dataset import PartitionedDataset
from repro.storage.query import run_query

ROOT = pathlib.Path(__file__).resolve().parents[1]
DOC = ROOT / "docs" / "METRICS.md"

# metric-name leaves that may follow a parametrized segment
_KERNEL_LEAVES = ("dispatches", "pallas_dispatches", "h2d_bytes",
                  "d2h_bytes")


def _canon(name: str) -> str:
    """Collapse parametrized segments to the doc's placeholder form."""
    m = re.fullmatch(r"kernel\.(.+)\.(%s)" % "|".join(_KERNEL_LEAVES), name)
    if m:
        return f"kernel.<kernel>.{m.group(2)}"
    if re.fullmatch(r"mesh\.shard\d+\.h2d_bytes", name):
        return "mesh.shard<k>.h2d_bytes"
    m = re.fullmatch(r"feed\.joint\.([^.]+)\.lag\.(.+)", name)
    if m:
        return "feed.joint.<joint>.lag.<sub>"
    m = re.fullmatch(r"feed\.joint\.([^.]+)\.(published|dropped)", name)
    if m:
        return f"feed.joint.<joint>.{m.group(2)}"
    m = re.fullmatch(r"feed\.sink\.([^.]+)\.(records|batch_records|backlog)",
                     name)
    if m:
        return f"feed.sink.<dataset>.{m.group(2)}"
    m = re.fullmatch(r"feed\.([^.]+)\.(records|batch_records)", name)
    if m:
        return f"feed.<feed>.{m.group(2)}"
    return name


def _workload():
    """Touch every family: lsm.* (flush/merge/pins), kernel.* +
    plan_cache.* + buffer_pool.* (warm loop queries), mesh.* + reshard
    (the same plan under a 1-device mesh), feed.* and serve.* (their
    smoke benches, which also start the exporter)."""
    from repro.core import adm
    PC.set_enabled(True)
    rt = adm.RecordType("MDocT", (adm.Field("id", adm.INT64),
                                  adm.Field("a", adm.INT64)), open=True)
    ds = PartitionedDataset("D", rt, "id", num_partitions=2,
                            flush_threshold=16,
                            merge_policy=TieredMergePolicy(k=2))
    ds.create_index("a")
    for i in range(80):
        ds.insert({"id": i, "a": i % 40})
    plan = A.aggregate(
        A.select(A.scan("D"), pred=lambda r: 5 <= r["a"] <= 25,
                 fields=["a"], ranges={"a": (5, 25)}, ranges_exact=True),
        {"c": ("count", "*"), "s": ("sum", "a")})
    for _ in range(2):
        run_query(plan, {"D": ds}, vectorize=True)
    for _ in range(2):
        run_query(plan, {"D": ds}, vectorize=True, mesh=1)
    ds.crash_and_recover()                  # lsm.wal_* replay metrics
    run_query(plan, {"D": ds}, vectorize=True)

    from benchmarks import feeds_bench
    feeds_bench.run(smoke=True)
    # a tiny serve session covers serve.* + SLO/phase metrics; the
    # exporter answers one scrape to register obs.exporter.scrapes
    from urllib.request import urlopen

    from repro.serve import ServeHarness
    srv = obs.serve_http()
    try:
        srt = adm.RecordType("MDocServeT", (adm.Field("pk", adm.INT64),
                                            adm.Field("val", adm.INT64)),
                             open=True)
        sds = PartitionedDataset("S", srt, "pk", num_partitions=2,
                                 flush_threshold=64,
                                 merge_policy=TieredMergePolicy(k=2))
        h = ServeHarness(sds, n_ingest=1, n_query=1, pump_batch=32,
                         records_per_lane=128, deadline_s=5.0)
        h.run(duration_s=10.0)
        urlopen(f"http://127.0.0.1:{srv.port}/metrics", timeout=5).read()
    finally:
        srv.stop()


def _emitted_names(out: pathlib.Path) -> list:
    """Every registry name after ``_workload()``, from a fresh
    interpreter."""
    code = ("import json, sys; import test_metrics_doc as m; m._workload(); "
            "from repro import obs; "
            "open(sys.argv[1], 'w').write(json.dumps(sorted(obs.snapshot())))")
    env = dict(os.environ)
    env["PYTHONPATH"] = os.pathsep.join(
        [str(ROOT / "tests"), str(ROOT / "src"), str(ROOT)]
        + ([env["PYTHONPATH"]] if env.get("PYTHONPATH") else []))
    proc = subprocess.run([sys.executable, "-c", code, str(out)], env=env,
                          cwd=str(ROOT), capture_output=True, text=True,
                          timeout=600)
    assert proc.returncode == 0, proc.stderr[-4000:]
    return json.loads(out.read_text())


def test_every_emitted_metric_is_documented(tmp_path):
    doc = DOC.read_text()
    documented = set(re.findall(r"`([a-z0-9_.<>]+)`", doc))
    emitted = {_canon(n) for n in _emitted_names(tmp_path / "names.json")}
    missing = sorted(n for n in emitted if n not in documented)
    assert not missing, \
        f"metrics emitted but not documented in docs/METRICS.md: {missing}"


def test_canonicalization_examples():
    assert _canon("kernel.spmd_index_chain.dispatches") \
        == "kernel.<kernel>.dispatches"
    assert _canon("kernel.dispatches") == "kernel.dispatches"
    assert _canon("mesh.shard3.h2d_bytes") == "mesh.shard<k>.h2d_bytes"
    assert _canon("feed.joint.j1.lag.subA") \
        == "feed.joint.<joint>.lag.<sub>"
    assert _canon("feed.sink.D.backlog") == "feed.sink.<dataset>.backlog"
    assert _canon("feed.f.records") == "feed.<feed>.records"
    assert _canon("buffer_pool.reshard_evictions") \
        == "buffer_pool.reshard_evictions"
