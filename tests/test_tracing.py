"""Span identity and request context, the profiler's shared clock, the
spans and counters at the read, ingest and recovery sites, and the
benchmark's per-layer readers of them (``bench/metrics``) on hand-made
inputs."""

import glob
import threading
import time
import tracemalloc
from types import SimpleNamespace

import pytest

from repro import obs
from repro.columnar import plancache as PC
from repro.core import adm
from repro.core import algebra as A
from repro.core.lsm import TieredMergePolicy
from repro.obs import tracer
from repro.storage.dataset import PartitionedDataset
from repro.storage.query import run_query


@pytest.fixture(autouse=True)
def _clean_tracer():
    obs.disable()
    obs.clear()
    yield
    obs.disable()
    obs.clear()


def _busy(cpu_seconds):
    """Spin until this thread has run ``cpu_seconds`` of CPU time."""
    t_end = time.thread_time() + cpu_seconds
    while time.thread_time() < t_end:
        pass


def _hist(name, key="count"):
    return (obs.snapshot().get(name) or {}).get(key, 0)


# ---------------------------------------------------------------------------
# the tracer: ids, parents, request ids, CPU time
# ---------------------------------------------------------------------------

def test_spans_carry_sid_parent_trace_id_and_cpu_across_threads():
    obs.enable()
    got = {}

    def work(tag):
        with obs.request() as tid:
            with obs.span("outer." + tag):
                with obs.span("inner." + tag):
                    _busy(0.03)
                with obs.span("wait." + tag):
                    time.sleep(0.05)
        got[tag] = tid

    threads = [threading.Thread(target=work, args=(t,)) for t in "ab"]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    evs = {e.name: e for e in obs.events()}
    assert len(evs) == 6
    assert len({e.sid for e in evs.values()}) == 6        # process-unique
    assert got["a"] != got["b"] and None not in got.values()
    for tag in "ab":
        outer = evs["outer." + tag]
        inner, wait = evs["inner." + tag], evs["wait." + tag]
        assert outer.parent is None and outer.depth == 0
        assert inner.parent == outer.sid and wait.parent == outer.sid
        assert {outer.trace_id, inner.trace_id, wait.trace_id} == {got[tag]}
        # busy work is CPU time; a sleep is wall time with next to none
        assert 0.03 <= inner.cpu_s <= inner.duration
        assert wait.cpu_s < 0.5 * wait.duration
        assert outer.cpu_s >= inner.cpu_s


def _current_trace_id():
    with obs.span("probe") as sp:
        pass
    return sp.trace_id


def test_request_context_ids():
    obs.enable()
    assert _current_trace_id() is None
    with obs.request() as t1:
        assert t1 is not None and _current_trace_id() == t1
        with obs.request() as kept:          # an open request is kept
            assert kept == t1
        with obs.request("given") as t2:     # an explicit id overrides
            assert t2 == "given" == _current_trace_id()
        assert _current_trace_id() == t1     # and is restored
    assert _current_trace_id() is None
    with obs.request() as t3:
        assert t3 != t1


def test_disabled_path_is_the_shared_noop_and_allocates_nothing(monkeypatch):
    assert not obs.enabled()
    assert obs.span("a") is tracer._NOOP
    assert obs.request() is obs.request()    # shared no-op request
    with obs.request() as tid:
        assert tid is None                   # no id made while disabled

    def no_clock():
        raise AssertionError("clock read on the disabled path")
    for _ in range(10):                      # warm every code path
        with obs.span("a") as s:
            s.set("k", 1)
    monkeypatch.setattr(tracer, "time", SimpleNamespace(
        perf_counter=no_clock, thread_time=no_clock))
    tracemalloc.start()
    try:
        before = tracemalloc.take_snapshot()
        for _ in range(2000):
            with obs.span("a") as s:
                s.add("n", 1)
        after = tracemalloc.take_snapshot()
    finally:
        tracemalloc.stop()
    only = [tracemalloc.Filter(True, tracer.__file__)]
    diff = after.filter_traces(only).compare_to(
        before.filter_traces(only), "lineno")
    assert sum(d.count_diff for d in diff) == 0
    assert obs.events() == []


def test_dump_trace_writes_ids_and_cpu(tmp_path):
    import json
    obs.enable()
    with obs.request(7):
        with obs.span("exec.SCAN"):
            with obs.span("lsm.flush"):
                pass
    assert obs.dump_trace(str(tmp_path / "t.json")) == 2
    evs = {e["name"]: e["args"]
           for e in json.load(open(tmp_path / "t.json"))["traceEvents"]}
    scan, flush = evs["exec.SCAN"], evs["lsm.flush"]
    assert scan["parent"] is None and flush["parent"] == scan["sid"]
    assert scan["trace_id"] == flush["trace_id"] == 7
    assert scan["cpu_s"] >= flush["cpu_s"] >= 0.0


# ---------------------------------------------------------------------------
# the profiler's clock
# ---------------------------------------------------------------------------

def test_spans_land_on_the_profiler_host_plane(tmp_path):
    """Each program span is a host-plane event of its own name with its
    ``trace_id``; its start, mapped by the benchmark's offset rule (the
    ``bench.window`` annotation's start plus the span's perf_counter
    offset from inside it), agrees within 1 ms."""
    import jax
    from bench import trace
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 1
    obs.enable()
    jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
    try:
        with jax.profiler.TraceAnnotation("bench.window"):
            pc0 = time.perf_counter()
            with obs.request(41):
                with obs.span("chain.operands"):
                    time.sleep(0.01)
                    with obs.span("chain.h2d"):
                        time.sleep(0.005)
                time.sleep(0.01)
                with obs.span("chain.device"):
                    _busy(0.01)
    finally:
        jax.profiler.stop_trace()
    spans = obs.events()
    path = sorted(glob.glob(str(tmp_path / "**" / "*.xplane.pb"),
                            recursive=True))[-1]
    prof = trace.load(path)
    host = {}
    for plane in prof.planes:
        if plane.name.startswith("/host:"):
            for line in plane.lines:
                for e in line.events:
                    host.setdefault(e.name, []).append(
                        (float(e.start_ns), dict(e.stats)))
    lo = host["bench.window"][0][0]
    assert len(spans) == 3
    for sp in spans:
        (start, stats), = host[sp.name]
        assert stats.get("trace_id") == 41
        mapped = lo + (sp.t0 - pc0) * 1e9
        assert abs(mapped - start) < 1e6, (sp.name, mapped - start)
    # the benchmark's host filter still keeps only its own annotations,
    # so the program's spans are not counted twice
    _devs, events = trace.planes(prof)
    assert {n for n, _s, _d in events if trace.HOST_NAMES.search(n)} \
        == {"bench.window"}


# ---------------------------------------------------------------------------
# the sites
# ---------------------------------------------------------------------------

def _rec_type(name):
    return adm.RecordType(name, (adm.Field("id", adm.INT64),
                                 adm.Field("a", adm.INT64)), open=True)


def _indexed(n=160, parts=2, threshold=32):
    ds = PartitionedDataset("D", _rec_type("TrT"), "id",
                            num_partitions=parts, flush_threshold=threshold,
                            merge_policy=TieredMergePolicy(k=99))
    ds.create_index("a")
    ds.insert_batch([{"id": i, "a": i % 50} for i in range(n)])
    return ds


def _select_plan():
    return A.select(A.scan("D"), pred=lambda r: 10 <= r["a"] <= 29,
                    fields=["a"], ranges={"a": (10, 29)}, ranges_exact=True)


def test_indexed_query_spans_share_one_trace_id():
    PC.set_enabled(True)
    ds = _indexed()
    run_query(_select_plan(), {"D": ds}, vectorize=True)   # warm
    obs.enable()
    rows, ex = run_query(_select_plan(), {"D": ds}, vectorize=True)
    rows2, _ = run_query(_select_plan(), {"D": ds}, vectorize=True)
    obs.disable()
    assert not ex.stats.fallback_reasons and len(rows) == len(rows2) > 0
    evs = obs.events()
    by_trace = {}
    for e in evs:
        by_trace.setdefault(e.trace_id, []).append(e)
    assert None not in by_trace and len(by_trace) == 2
    for spans in by_trace.values():
        names = {e.name for e in spans}
        assert {"query.optimize", "exec.POST_VALIDATE_SELECT",
                "columnar.POST_VALIDATE_SELECT", "columnar.to_rows",
                "chain.operands", "chain.h2d", "chain.device",
                "chain.assemble"} <= names
        roots = [e for e in spans if e.depth == 0]
        assert sorted(e.name for e in roots) == [
            "exec.POST_VALIDATE_SELECT", "query.optimize"]
        col = next(e for e in spans
                   if e.name == "columnar.POST_VALIDATE_SELECT")
        decode = next(e for e in spans if e.name == "columnar.to_rows")
        assert decode.parent == col.sid
        assert decode.attrs["rows"] == len(rows)
        sids = {e.sid: e for e in spans}
        for e in spans:
            if e.name.startswith("chain."):
                # every chain span nests under the columnar operator
                p = sids[e.parent]
                while p.sid != col.sid:
                    p = sids[p.parent]
        assert sum(e.name == "chain.device" for e in spans) == 2  # 2 parts


def test_feed_through_serve_harness_moves_ingest_metrics():
    from repro.serve import ServeHarness
    rt = adm.RecordType("TrServeT", (adm.Field("pk", adm.INT64),
                                     adm.Field("val", adm.INT64)), open=True)
    ds = PartitionedDataset("S", rt, "pk", num_partitions=2,
                            flush_threshold=64,
                            merge_policy=TieredMergePolicy(k=2))
    keys = ("storage.insert_validate_seconds",
            "storage.insert_apply_seconds", "serve.sink.put_wait_seconds")
    n0 = {k: _hist(k) for k in keys}
    obs.enable()
    h = ServeHarness(ds, n_ingest=1, n_query=1, pump_batch=32,
                     records_per_lane=256)
    rep = h.run(duration_s=1.5)
    obs.disable()
    assert rep.ingest_acked > 0 and not rep.query_errors
    batches = rep.ingest_acked // 32
    for k in keys:
        assert _hist(k) - n0[k] >= batches, k
    evs = obs.events()
    sids = {e.sid: e for e in evs}
    names = [e.name for e in evs]
    for n in ("storage.insert_batch", "storage.insert_validate",
              "storage.insert_apply", "feed.intake", "feed.store"):
        assert n in names, n
    for e in evs:
        if e.name in ("storage.insert_validate", "storage.insert_apply"):
            assert sids[e.parent].name == "storage.insert_batch"
        if e.name in ("feed.intake", "feed.store"):
            assert sids[e.parent].name.startswith("feed.pump.")


def test_serve_request_id_reaches_the_program_spans():
    """``RequestTracker.begin`` opens the request context with the
    record's own trace id until ``settle``: the query's program spans
    and the profiled request's spans all carry it."""
    from repro.serve import RequestTracker
    PC.set_enabled(True)
    ds = _indexed()
    tr = RequestTracker(profile_every=1)
    obs.enable()
    rec = tr.begin("query")
    with rec.phase("execute"):
        run_query(_select_plan(), {"D": ds}, vectorize=True)
    tr.settle(rec)
    obs.disable()
    with obs.request() as after:
        assert after is None                        # closed by settle
    evs = obs.events()
    assert any(e.name == "chain.device" for e in evs)
    assert {e.trace_id for e in evs} == {rec.trace_id}
    assert {sp.name for sp in rec.spans} == {"serve.request",
                                             "serve.phase.execute"}
    assert {sp.trace_id for sp in rec.spans} == {rec.trace_id}
    root = next(e for e in evs if e.name.startswith("exec."))
    assert root.depth == 2            # under serve.request and its phase


def test_crash_and_recover_counts_the_wal():
    ds = PartitionedDataset("W", _rec_type("TrWalT"), "id",
                            num_partitions=3, flush_threshold=40)
    ds.insert_batch([{"id": i, "a": i % 7} for i in range(100)])
    wal = sum(len(p.primary.wal) for p in ds.partitions)
    assert wal == 100
    r0 = obs.snapshot()["lsm.wal_records_replayed"]
    h0 = _hist("lsm.wal_replay_seconds")
    obs.enable()
    ds.crash_and_recover()
    obs.disable()
    assert obs.snapshot()["lsm.wal_records_replayed"] - r0 == wal
    assert _hist("lsm.wal_replay_seconds") - h0 == ds.num_partitions
    replays = [e for e in obs.events() if e.name == "lsm.wal_replay"]
    assert sum(e.attrs["records"] for e in replays) == wal
    # the replayed memtables are shredded by the next columnar scan
    mem = sum(len(p.primary.memtable) for p in ds.partitions)
    assert mem > 0
    s0 = _hist("storage.memtable_shred_seconds")
    obs.enable()
    for i in range(ds.num_partitions):
        ds.scan_partition_batch(i)
        ds.scan_partition_batch(i)               # cached: no second shred
    obs.disable()
    assert _hist("storage.memtable_shred_seconds") - s0 == ds.num_partitions
    shreds = [e for e in obs.events() if e.name == "storage.memtable_shred"]
    assert sum(e.attrs["rows"] for e in shreds) == mem


# ---------------------------------------------------------------------------
# the benchmark's readers, on hand-made spans and snapshots
# ---------------------------------------------------------------------------

def _reader(name):
    from bench import run
    return run.load_reader(name)


def _sp(name, t0, t1, depth=1, cpu_s=0.0):
    return SimpleNamespace(name=name, t0=t0, t1=t1, depth=depth, cpu_s=cpu_s)


def _cell(spans=(), obs0=None, obs1=None, **kw):
    c = SimpleNamespace(
        spans=list(spans), pc0=10.0, pc1=30.0,
        requests=[SimpleNamespace(t_done=t) for t in (11.0, 20.0, 29.0,
                                                      31.0)],
        obs0=obs0 or {}, obs1=obs1 or {}, setup_s=50.0, e2e={},
        mix={"lanes": 2})
    for k, v in kw.items():
        setattr(c, k, v)
    return c


READS_SPANS = [
    _sp("query.optimize", 10.0, 10.002, depth=0, cpu_s=0.002),
    _sp("exec.POST_VALIDATE_SELECT", 10.002, 10.8, depth=0, cpu_s=0.1),
    _sp("chain.operands", 10.1, 10.2), _sp("chain.operands", 10.3, 10.35),
    _sp("chain.h2d", 10.2, 10.21),
    _sp("chain.device", 10.21, 10.3), _sp("chain.device", 10.35, 10.36),
    _sp("chain.assemble", 10.36, 10.38),
    _sp("columnar.to_rows", 10.4, 10.52),
    _sp("exec.POST_VALIDATE_SELECT", 11.0, 11.5, depth=0, cpu_s=0.198),
]


@pytest.mark.parametrize("name,want", [
    # three requests finish inside [pc0, pc1]
    ("chain_prep_ms.reads", (0.1 + 0.05 + 0.01) / 3 * 1e3),
    ("chain_dispatch_ms.reads", (0.09 + 0.01) / 3 * 1e3),
    ("row_decode_ms.reads", (0.02 + 0.12) / 3 * 1e3),
    ("host_cpu_ms.reads", (0.002 + 0.1 + 0.198) / 3 * 1e3),
])
def test_read_cell_span_readers(name, want):
    read = _reader(name)
    assert read(_cell(READS_SPANS)) == pytest.approx(want)
    assert read(_cell([])) is None
    assert read(_cell(READS_SPANS, requests=[])) is None


def test_host_cpu_reader_needs_cpu_time():
    bare = [SimpleNamespace(name=s.name, t0=s.t0, t1=s.t1, depth=s.depth)
            for s in READS_SPANS]
    assert _reader("host_cpu_ms.reads")(_cell(bare)) is None


@pytest.mark.parametrize("name,key", [
    ("insert_validate_share.ingest", "storage.insert_validate_seconds"),
    ("sink_wait_share.ingest", "serve.sink.put_wait_seconds"),
])
def test_ingest_share_readers(name, key):
    read = _reader(name)
    c = _cell(obs0={key: {"sum": 3.0}}, obs1={key: {"sum": 11.0}})
    # 8 s of 2 lanes x 20 s
    assert read(c) == pytest.approx(20.0)
    assert read(_cell(obs1={key: {"sum": 4.0}})) == pytest.approx(10.0)
    assert read(_cell()) is None


def test_wal_replay_share_reads_the_registry_after_recovery(monkeypatch):
    _check_recovery_share(monkeypatch, "wal_replay_share.recovery",
                          "lsm.wal_replay_seconds")


def test_memtable_shred_share_reads_the_registry_after_recovery(
        monkeypatch):
    _check_recovery_share(monkeypatch, "memtable_shred_share.recovery",
                          "storage.memtable_shred_seconds")


def _check_recovery_share(monkeypatch, name, key):
    read = _reader(name)
    monkeypatch.setattr(obs, "snapshot", lambda: {key: {"sum": 9.5}})
    c = _cell(obs1={key: {"sum": 1.5}}, e2e={"recovery_s": 32.0})
    assert read(c) == pytest.approx(25.0)
    assert read(_cell(e2e={"recovery_s": 32.0})) == \
        pytest.approx(9.5 / 32.0 * 100.0)
    assert read(_cell(obs1={key: {"sum": 1.5}})) is None   # no recovery
    monkeypatch.setattr(obs, "snapshot", lambda: {})
    assert read(c) is None


def test_load_insert_share_reader():
    read = _reader("load_insert_share.setup")
    c = _cell(obs0={"storage.insert_validate_seconds": {"sum": 12.0},
                    "storage.insert_apply_seconds": {"sum": 18.0}})
    assert read(c) == pytest.approx(60.0)     # 30 s of a 50 s set-up
    assert read(_cell(obs0={"storage.insert_apply_seconds":
                            {"sum": 18.0}})) is None
    assert read(_cell()) is None
