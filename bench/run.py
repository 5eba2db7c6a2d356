#!/usr/bin/env python3
"""Benchmark of the columnar BDMS on a TPU, one cell per process.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

A cell (an entry of ``workloads`` in ``BENCHMARK.json``) is a deployment
(``bench/configs/<config>.json``) under a traffic mix
(``bench/mixes/<traffic>.json``).  The run builds the deployment's
dataverse from the seed through the program's ``insert_batch``, warms up
the shapes its mix uses, measures for ``--seconds`` from the client's
side, then checks what the window produced against the plain reference
(``bench/reference.py``) and prints one JSON line.  ``--trace 1`` runs
the same window under the JAX profiler and the program's span tracer and
reports the per-layer metrics (``bench/metrics/<name>.py``) in place of
the end-to-end ones.

A configuration may name ``mesh``: the number of chips its partitions
span, the deployment's cluster layout mapped onto the cell's 1 or 4
chips.  Without it the run builds no mesh and every query runs the
program's per-partition loop.  With it (an integer of at least 2 that
divides ``partitions`` and is at most the cell's ``chips``) the run
builds one ``repro.runtime.spmd.partition_mesh`` and hands it to every
program query (warm-up, clients, feed poller, recovery probe) as
``run_query(..., mesh=...)``; the program's SPMD paths do the rest.
Each result reports under ``info["mesh"]`` the mesh's size and the
window's ``mesh.*`` counter deltas (SPMD dispatches, partitions they
covered, fallbacks to the loop).  The trace reduction
(``bench/trace.py``) already averages device busy time over every chip
in the trace, that is over the cell's chips.

It refuses to run (exit 2, no result) unless JAX's first device is a
TPU and there are as many as the cell asks for, and before any set-up
where the configuration's ``mesh`` is not such a value.  JAX's
persistent compilation cache lives in ``<checkout>/.jax_cache``.
"""

from __future__ import annotations

import argparse
import importlib.util
import json
import os
import pathlib
import statistics
import sys
import tempfile
import threading
import time
from typing import Any, Callable, Dict, List, Optional

ROOT = pathlib.Path(__file__).resolve().parents[1]
BENCH = ROOT / "bench"
CACHE_DIR = ROOT / ".jax_cache"


def log(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


def load_spec() -> Dict[str, Any]:
    return json.loads((ROOT / "BENCHMARK.json").read_text())


def load_config(name: str) -> Dict[str, Any]:
    return json.loads((BENCH / "configs" / f"{name}.json").read_text())


def mesh_devices(cfg: Dict[str, Any], cell: Dict[str, Any]) -> int:
    """The chips the deployment's partitions span: 0 (no mesh) where the
    configuration has no ``mesh``; otherwise its ``mesh``, which must be
    an integer of at least 2 that divides ``partitions`` and is at most
    the cell's ``chips``."""
    if "mesh" not in cfg:
        return 0
    n, parts, chips = cfg["mesh"], int(cfg["partitions"]), int(cell["chips"])
    if type(n) is not int or n < 2 or parts % n or n > chips:
        raise ValueError(
            f"bench: configuration {cell['config']!r}: "
            f"'mesh' must be an integer >= 2 that divides partitions "
            f"({parts}) and is at most the cell's chips ({chips}), "
            f"not {n!r}")
    return n


def load_reader(name: str) -> Callable[[Any], Optional[float]]:
    path = BENCH / "metrics" / f"{name}.py"
    spec = importlib.util.spec_from_file_location(f"bench_metric_{name}",
                                                  path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


# ---------------------------------------------------------------------------
# the deployment
# ---------------------------------------------------------------------------

class Deployment:
    """The program's dataverse for one configuration, built from the
    seed, with the generated columns the reference reads."""

    def __init__(self, cfg: Dict[str, Any], seed: int):
        from bench import gen
        self.cfg = cfg
        self.seed = seed
        self.n_users = int(cfg["users"])
        self.n_messages = int(cfg["messages"])
        self.users = gen.users(self.n_users, seed)
        self.msgs = gen.messages(self.n_messages, self.n_users, seed)
        self.datasets: Dict[str, Any] = {}

    def build(self) -> None:
        from bench import gen
        from repro.configs import tinysocial as ts
        from repro.core.lsm import TieredMergePolicy
        from repro.storage.dataset import PartitionedDataset
        cfg = self.cfg
        types = {"MugshotUsers": ts.user_type(),
                 "MugshotMessages": ts.message_type()}
        if cfg["schema"] == "key_only":
            types = {n: t.key_only(cfg["datasets"][n]["primary_key"])
                     for n, t in types.items()}
        merge = cfg["merge"]
        if merge["policy"] == "tiered":
            policy = TieredMergePolicy(k=int(merge["k"]),
                                       ratio=float(merge["ratio"]))
        else:                     # flushes accumulate; one forced merge
            policy = TieredMergePolicy(k=1 << 30)
        records = {"MugshotUsers": lambda: gen.user_records(self.users),
                   "MugshotMessages": lambda: gen.message_records(self.msgs)}
        step = int(cfg["load_batch"])
        for name in ("MugshotUsers", "MugshotMessages"):
            dcfg = cfg["datasets"][name]
            ds = PartitionedDataset(name, types[name], dcfg["primary_key"],
                                    int(cfg["partitions"]),
                                    int(dcfg["flush_threshold"]),
                                    merge_policy=policy)
            for idx in dcfg["indexes"]:
                ds.create_index(idx["field"], idx["kind"],
                                int(idx.get("gram_length", 3)))
            recs = records[name]()
            for j in range(0, len(recs), step):
                ds.insert_batch(recs[j:j + step])
            del recs
            if merge["policy"] == "forced_oldest_pair":
                for part in ds.partitions:
                    comps = [c for c in part.primary.components if c.valid]
                    if len(comps) >= 2:
                        part.primary.merge(comps[-2:])
            self.datasets[name] = ds


# ---------------------------------------------------------------------------
# requests
# ---------------------------------------------------------------------------

class Request:
    __slots__ = ("req", "t_due", "t_issue", "t_done", "rows", "error",
                 "fallback", "among", "snap_ok", "bg", "traces")

    def __init__(self, req: Dict[str, Any], t_due: float, bg: bool = False):
        self.req = req
        self.t_due = t_due
        self.t_issue = self.t_done = 0.0
        self.rows = None
        self.error: Optional[str] = None
        self.fallback = False
        self.among = None
        self.snap_ok = True
        self.bg = bg
        self.traces = 0

    @property
    def latency(self) -> float:
        return self.t_done - self.t_due

    @property
    def failed(self) -> bool:
        return self.error is not None or self.fallback


# ---------------------------------------------------------------------------
# traffic drivers
# ---------------------------------------------------------------------------

class Cell:
    """One run of one cell: set-up, window, checks, metrics."""

    def __init__(self, spec: Dict[str, Any], cell: Dict[str, Any],
                 seed: int, seconds: float, trace: bool,
                 overrides: Optional[Dict[str, Any]] = None):
        from bench import traffic
        self.spec = spec
        self.cell = cell
        self.cfg = load_config(cell["config"])
        self.cfg.update(overrides or {})
        self.mesh_devices = mesh_devices(self.cfg, cell)
        self.mesh = None
        if self.mesh_devices:
            from repro.runtime.spmd import partition_mesh
            self.mesh = partition_mesh(self.mesh_devices)
        self.mix = traffic.load_mix(cell["traffic"])
        self.seed = int(seed)
        self.seconds = float(seconds)
        self.trace = bool(trace)
        self.requests: List[Request] = []
        self.checks: Dict[str, Dict[str, Any]] = {}
        self.info: Dict[str, Any] = {}
        self.e2e: Dict[str, float] = {}

    # -- the timed path ---------------------------------------------------------
    def _execute(self, r: Request, plan, datasets: Dict[str, Any]) -> None:
        """One request through the program's query entry, timed from its
        due time to its last row."""
        from repro.storage.query import run_query
        import jax
        r.t_issue = time.perf_counter()
        try:
            with jax.profiler.TraceAnnotation(f"bench.{r.req['template']}"):
                rows, ex = run_query(plan, datasets, vectorize=True,
                                     snapshot=True, mesh=self.mesh)
            r.fallback = bool(ex.stats.fallback_reasons)
            r.traces = ex.stats.kernel_retraces
            if r.req["check"]:
                r.rows = rows
        except Exception as e:                       # noqa: BLE001
            r.error = f"{type(e).__name__}: {e}"
        r.t_done = time.perf_counter()

    # -- set-up ---------------------------------------------------------------
    def setup(self) -> None:
        from bench import traffic
        self.dep = Deployment(self.cfg, self.seed)
        self.dep.build()
        self.dom = traffic.domains(self.dep.n_users)
        self.templates = {t["name"]: t for t in self._all_templates()}
        if self.mix["loop"] == "feed":
            self._setup_feed()
        self._warmup()

    def _all_templates(self) -> List[dict]:
        mix = self.mix
        if mix["loop"] == "closed":
            return mix["templates"]
        return mix["background"]["templates"] + [mix["recovery_probe"]]

    def _warmup(self) -> None:
        """Every template of the mix through the same entry as the window,
        from a stream of its own: ``warmup`` draws, or fewer where
        ``warmup_quiet`` draws in a row have traced no program.  A
        template with ``warmup_replay`` then runs its requests among the
        first ``warmup_replay`` draws of each client's own stream too:
        the shapes the window meets, where rare ones escape the draws."""
        from bench import traffic
        ts = self.mix["templates"] if self.mix["loop"] == "closed" \
            else self.mix["background"]["templates"]
        drawn: Dict[str, List[int]] = {}
        for j, t in enumerate(ts):
            n = int(t["warmup"] if "warmup" in t else self.mix["warmup"])
            quiet = int(t.get("warmup_quiet", n))
            d = traffic.Drawer(self.mix, self.dep.n_users, self.seed,
                               traffic.WARMUP_STREAM + j, templates=[t])
            last = -1
            for k in range(n):
                if self._warm(d.next()):
                    last = k
                if k - last >= quiet:
                    break
            drawn[t["name"]] = [k + 1, last, 0, 0]
        replay = {t["name"]: int(t["warmup_replay"]) for t in ts
                  if "warmup_replay" in t}
        for c in range(int(self.mix["clients"]) if replay else 0):
            d = traffic.Drawer(self.mix, self.dep.n_users, self.seed,
                               traffic.CLIENT_STREAM + c)
            for k in range(max(replay.values())):
                req = d.next()
                if k < replay.get(req["template"], 0):
                    row = drawn[req["template"]]
                    row[2] += 1
                    row[3] += int(self._warm(req))
        # per template: draws, the last that traced, replayed, of them traced
        self.info["warmup_draws"] = drawn

    def _warm(self, req: Dict[str, Any]) -> bool:
        """One warm-up request; whether it traced a program."""
        from bench import traffic
        t = self.templates[req["template"]]
        req["check"] = False
        r = Request(req, time.perf_counter())
        self._execute(r, traffic.plan(req, t, self.dom), self.dep.datasets)
        if r.error:
            raise RuntimeError(f"warm-up {t['name']}: {r.error}")
        return bool(r.traces)

    # -- the window -----------------------------------------------------------
    def window(self) -> None:
        import jax
        from repro import obs
        from repro.runtime.compile_cache import CompileMeter
        meter = CompileMeter()
        tmp = None
        if self.trace:
            tmp = tempfile.mkdtemp(prefix="bench-trace-")
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0
            opts.host_tracer_level = 1
            obs.enable()
            jax.profiler.start_trace(tmp, profiler_options=opts)
        self.obs0 = obs.snapshot()
        c0 = meter.snapshot()
        with jax.profiler.TraceAnnotation("bench.window"):
            self.pc0 = time.perf_counter()
            if self.mix["loop"] == "closed":
                self._closed_loop()
            else:
                self._feed_window()
            self.pc1 = time.perf_counter()
        self.obs1 = obs.snapshot()
        c1 = meter.snapshot()
        self.info["window_compiles"] = c1[0] - c0[0] + c1[1] - c0[1]
        self.info["window_compile_s"] = round(c1[2] - c0[2], 6)
        self.info["mesh"] = {"devices": self.mesh_devices}
        for k in ("spmd_dispatches", "spmd_partitions", "spmd_fallbacks"):
            key = f"mesh.{k}"
            self.info["mesh"][k] = (self.obs1.get(key, 0)
                                    - self.obs0.get(key, 0))
        self.spans = []
        self.trace_red = None
        if self.trace:
            jax.profiler.stop_trace()
            obs.disable()
            self.spans = obs.events()
            obs.clear()
            self.trace_red = self._reduce_trace(tmp)

    def _closed_loop(self) -> None:
        from bench import traffic
        mix, t_end = self.mix, time.perf_counter() + self.seconds
        self.t_close = t_end
        lock = threading.Lock()
        datasets = self.dep.datasets

        def client(c: int) -> None:
            d = traffic.Drawer(mix, self.dep.n_users, self.seed,
                               traffic.CLIENT_STREAM + c)
            while time.perf_counter() < t_end:
                req = d.next()
                plan = traffic.plan(req, self.templates[req["template"]],
                                    self.dom)
                r = Request(req, time.perf_counter())
                self._execute(r, plan, datasets)
                with lock:
                    self.requests.append(r)

        threads = [threading.Thread(target=client, args=(c,), name=f"c{c}")
                   for c in range(int(mix["clients"]))]
        for t in threads:
            t.start()
        for t in threads:
            t.join()

    # -- feed ingest ------------------------------------------------------------
    def _setup_feed(self) -> None:
        from bench import gen
        from repro.serve import ServeHarness
        mix = self.mix
        pool = int(mix["feed_pool"])
        n0 = self.dep.n_messages
        self.feed = gen.messages(pool, self.dep.n_users, self.seed,
                                 first_id=n0, stream=gen.FEED_STREAM)
        feed = self.feed
        ds = self.dep.datasets["MugshotMessages"]
        lanes = int(mix["lanes"])
        self.harness = ServeHarness(
            ds, n_ingest=lanes, n_query=0, pump_batch=int(mix["pump_batch"]),
            records_per_lane=pool // lanes,
            make_record=lambda pk: gen.message_record(feed, pk),
            vectorize=True)
        # a first burst: every partition leaves its preload bucket and
        # the feed path runs once before the window
        self.harness.start()
        want = int(mix["warmup_records"])
        while self._acked() < want:
            time.sleep(0.01)
        self.harness._quiesce()

    def _acked(self) -> int:
        h = self.harness
        with h._ack_lock:
            return sum(len(a) for a in h.acked)

    def _acked_sets(self) -> List[set]:
        h = self.harness
        with h._ack_lock:
            return [set(a) for a in h.acked]

    def _feed_window(self) -> None:
        """The lanes run free while one client polls in a closed loop: it
        pins a snapshot, checks it, runs the next background query over
        it, and goes on until the close (the query in flight then
        finishes).  A query is timed from its pin to its last row; the
        check in between reads the pk arrays that the query reads next,
        cached per pinned version.  Acks are counted at the close by a
        timer."""
        from bench import traffic
        mix, h = self.mix, self.harness
        ds = self.dep.datasets["MugshotMessages"]
        users = self.dep.datasets["MugshotUsers"]
        d = traffic.Drawer(mix["background"], self.dep.n_users, self.seed,
                           traffic.CLIENT_STREAM)
        closed: Dict[str, float] = {}
        h._gate.set()
        t0 = time.perf_counter()
        a0 = self._acked()
        t_end = self.t_close = t0 + self.seconds
        timer = threading.Timer(self.seconds,
                                lambda: closed.update(
                                    acked=self._acked(),
                                    late=time.perf_counter() - t_end))
        timer.start()
        while time.perf_counter() < t_end:
            req = d.next()
            t = self.templates[req["template"]]
            r = Request(req, time.perf_counter(), bg=True)
            floors = [len(a) for a in self._acked_sets()]
            snap = ds.pin()
            try:
                self._snapshot_check(r, snap, floors)
                self._execute(r, traffic.plan(req, t, self.dom),
                              {"MugshotMessages": snap, "MugshotUsers": users})
            finally:
                snap.release()
            self.requests.append(r)
        timer.join()
        h._quiesce()                     # park the lanes until the checks
        self.acked_in_window = closed["acked"] - a0
        self.info["close_count_late_s"] = round(closed["late"], 6)
        self.e2e["ingest_rate"] = self.acked_in_window / self.seconds
        pool = int(mix["feed_pool"])
        self.checks["feed_ran_dry"] = {
            "value": int(closed["acked"] >= pool), "limit": 0}

    def _snapshot_check(self, r: Request, snap, floors: List[int]) -> None:
        """The snapshot's message ids: every preloaded one, and of each
        lane a prefix of its sequence at least as long as was acked
        before the pin."""
        import numpy as np
        n0, lanes = self.dep.n_messages, int(self.mix["lanes"])
        pks = np.concatenate([np.asarray(snap.partition_pk_array(i),
                                         dtype=np.int64)
                              for i in range(snap.num_partitions)])
        pre = int(np.count_nonzero(pks < n0))
        fed = pks[pks >= n0] - n0
        ok = pre == n0
        for lane in range(lanes):
            lp = fed[fed % lanes == lane] // lanes
            if lp.size and int(lp.max()) != lp.size - 1:
                ok = False
            if lp.size < floors[lane]:
                ok = False
        r.snap_ok = ok
        among = np.zeros(n0 + self.feed["message-id"].size, dtype=bool)
        among[pks[pks < among.size]] = True
        r.among = among

    # -- after the window -----------------------------------------------------
    def finish(self) -> Dict[str, Any]:
        import jax
        from bench import reference
        devs = jax.devices()
        self.device_kind = devs[0].device_kind
        self.memory_peak = max(int((d.memory_stats() or {}).get(
            "peak_bytes_in_use", 0)) for d in devs)
        if self.mix["loop"] == "feed":
            self._finish_feed()
            msgs = reference.Reference(self.dep.users, self._all_messages())
        else:
            msgs = reference.Reference(self.dep.users, self.dep.msgs)
            self._read_metrics()
        self._check_answers(msgs)
        return self._result(devs)

    def _read_metrics(self) -> None:
        done = [r for r in self.requests
                if r.t_done <= self.t_close and not r.failed]
        self.e2e["query_throughput"] = len(done) / self.seconds
        lat = sorted(r.latency for r in done)
        if len(lat) >= 20:
            self.e2e["query_p95_ms"] = statistics.quantiles(
                lat, n=20, method="inclusive")[18] * 1e3

    def _all_messages(self):
        from bench import gen
        return gen.concat(self.dep.msgs, self.feed)

    def _finish_feed(self) -> None:
        """Stop the lanes, read every acknowledged record back, crash and
        recover, time the first answer after it (the recovery probe), read
        back again."""
        import numpy as np
        from bench import traffic
        self.harness.stop()
        acked = self._acked_sets()
        ds = self.dep.datasets["MugshotMessages"]
        self._readback("", ds, acked)
        probe = self.mix["recovery_probe"]
        d = traffic.Drawer({"templates": [probe]}, self.dep.n_users,
                           self.seed, traffic.CLIENT_STREAM + 1,
                           templates=[probe])
        req = d.next()
        req["check"] = True
        plan = traffic.plan(req, probe, self.dom)
        among = np.zeros(self.dep.n_messages + self.feed["message-id"].size,
                         dtype=bool)
        among[:self.dep.n_messages] = True
        for a in acked:
            among[np.fromiter(a, dtype=np.int64, count=len(a))] = True
        t0 = time.perf_counter()
        self._recover(ds)
        r = Request(req, t0)
        r.among = among
        self._execute(r, plan, self.dep.datasets)
        self.e2e["recovery_s"] = r.t_done - t0
        self.requests.append(r)
        self._readback("after_recovery", ds, acked)
        self.info["feed_records_acked"] = sum(len(a) for a in acked)

    def _recover(self, ds) -> None:
        ds.crash_and_recover()

    def _readback(self, tag: str, ds, acked: List[set]) -> None:
        """Every acked id present and nothing else; a seeded sample of
        records equal to what was written."""
        import numpy as np
        from bench import gen, reference
        n0 = self.dep.n_messages
        pks = np.concatenate([np.asarray(ds.partition_pk_array(i),
                                         dtype=np.int64)
                              for i in range(ds.num_partitions)])
        want = np.concatenate([np.arange(n0, dtype=np.int64)] + [
            np.fromiter(a, dtype=np.int64, count=len(a)) for a in acked])
        have = np.unique(pks)
        lost = np.setdiff1d(want, have, assume_unique=False).size
        extra = np.setdiff1d(have, want, assume_unique=False).size
        all_msgs = self._all_messages()
        rng = np.random.default_rng([self.seed, 77])
        sample = rng.choice(want, size=min(int(self.mix["check_records"]),
                                           want.size), replace=False)
        wrong = 0
        for pk in sample.tolist():
            got = ds.lookup(pk)
            exp = gen.message_record(all_msgs, pk)
            if got is None or reference.canon(got) != reference.canon(exp):
                wrong += 1
        sfx = f"_{tag}" if tag else ""
        self.checks[f"lost_acked{sfx}"] = {"value": lost, "limit": 0}
        self.checks[f"phantom_records{sfx}"] = {"value": extra, "limit": 0}
        self.checks[f"wrong_records{sfx}"] = {"value": wrong, "limit": 0}

    def _check_answers(self, ref) -> None:
        checked = [r for r in self.requests if r.req["check"]
                   and r.error is None]
        wrong = 0
        for r in checked:
            t = self.templates[r.req["template"]]
            want = ref.answer(r.req, t, among=r.among)
            got = reference_canon(r.rows)
            if got != want:
                wrong += 1
                self.info.setdefault("wrong", []).append(
                    [r.req["template"], len(got), len(want),
                     str(sorted(set(got) ^ set(want))[:2])[:600]])
        templates = set(self.templates)
        seen = {r.req["template"] for r in checked}
        failed = [r for r in self.requests if r.failed]
        self.checks["wrong_answers"] = {"value": wrong, "limit": 0}
        self.checks["failed_requests"] = {"value": len(failed), "limit": 0}
        self.checks["templates_unchecked"] = {
            "value": len(templates - seen), "limit": 0}
        if self.mix["loop"] == "feed":
            torn = sum(1 for r in self.requests if not r.snap_ok)
            self.checks["torn_snapshots"] = {"value": torn, "limit": 0}
        traced: Dict[str, int] = {}
        for r in self.requests:
            if r.traces:
                traced[r.req["template"]] = traced.get(r.req["template"],
                                                       0) + 1
        self.info["traced_requests"] = traced
        self.info["answers_checked"] = len(checked)
        self.info["errors"] = sorted({r.error for r in failed
                                      if r.error})[:3]

    # -- the result line ---------------------------------------------------------
    def _reduce_trace(self, tmp: str) -> Dict[str, Any]:
        import glob
        import shutil
        from bench import trace
        try:
            path = sorted(glob.glob(os.path.join(
                tmp, "**", "*.xplane.pb"), recursive=True))[-1]
            prof = trace.load(path)
            _devs, host = trace.planes(prof)
            win = [h for h in host if h[0] == "bench.window"]
            lo, hi = (win[0][1], win[0][1] + win[0][2]) if win else (None,
                                                                     None)
            extra = []
            if win:      # program spans onto the trace clock
                for sp in self.spans:
                    s = lo + (sp.t0 - self.pc0) * 1e9
                    extra.append((sp.name, s, (sp.t1 - sp.t0) * 1e9))
            return trace.reduce(prof, lo, hi, host_extra=extra)
        finally:
            shutil.rmtree(tmp, ignore_errors=True)

    def _result(self, devs) -> Dict[str, Any]:
        d0 = devs[0]
        device: Dict[str, Any] = {"platform": d0.platform,
                                  "kind": d0.device_kind, "count": len(devs),
                                  "memory_peak_bytes": self.memory_peak}
        metrics: Dict[str, Dict[str, Any]] = {}
        out: Dict[str, Any] = {}
        if self.trace:
            tr = self.trace_red or {}
            device["busy_s"] = tr.get("busy_s", 0.0)
            device["window_s"] = tr.get("window_s", self.pc1 - self.pc0)
            for m in self.spec["per_layer"]:
                if "workloads" in m and self.cell["name"] not in m["workloads"]:
                    continue
                v = load_reader(m["name"])(self)
                if v is not None:
                    metrics[m["name"]] = {"value": v, "unit": m["unit"]}
            from bench import trace
            out["breakdown"] = {
                "device_ops": trace.top(tr.get("device_s", {})),
                "idle_gaps": trace.top(tr.get("idle_gaps", {}))}
        else:
            self.e2e["setup_s"] = self.setup_s
            for m in self.spec["end_to_end"]:
                if "workloads" in m and self.cell["name"] not in m["workloads"]:
                    continue
                if m["name"] in self.e2e:
                    metrics[m["name"]] = {"value": self.e2e[m["name"]],
                                          "unit": m["unit"]}
        attempted = len(self.requests) + getattr(self, "acked_in_window", 0)
        failed = sum(1 for r in self.requests if r.failed)
        correct = all(c["value"] <= c["limit"] for c in self.checks.values())
        line = {"correct": correct, "attempted": attempted,
                "failed": failed, "metrics": metrics, "device": device}
        line.update(out)
        line["info"] = self.info
        line["checks"] = self.checks
        return line


def reference_canon(rows) -> list:
    from bench import reference
    return reference.canon_rows(rows or [])


def run_cell(spec: Dict[str, Any], name: str, seed: int, seconds: float,
             trace: bool, overrides: Optional[Dict[str, Any]] = None,
             cell_cls: type = Cell) -> Dict[str, Any]:
    """Everything after the look for a chip: set-up, window, checks."""
    cell = next(w for w in spec["workloads"] if w["name"] == name)
    t0 = time.perf_counter()
    c = cell_cls(spec, cell, seed, seconds, trace, overrides)
    c.setup()
    c.setup_s = time.perf_counter() - t0
    c.window()
    return c.finish()


def main(argv: Optional[List[str]] = None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    sys.path[:0] = [str(ROOT), str(ROOT / "src")]
    spec = load_spec()
    cell = next((w for w in spec["workloads"]
                 if w["name"] == args.workload), None)
    if cell is None:
        log(f"bench: no workload {args.workload!r} in BENCHMARK.json")
        return 2
    try:
        mesh_devices(load_config(cell["config"]), cell)
    except ValueError as e:
        log(str(e))
        return 2
    os.environ["JAX_COMPILATION_CACHE_DIR"] = str(CACHE_DIR)
    import jax
    jax.config.update("jax_compilation_cache_dir", str(CACHE_DIR))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    devs = jax.devices()
    if devs[0].platform != "tpu":
        log(f"bench: no TPU (JAX sees {devs[0].platform})")
        return 2
    if len(devs) < int(cell["chips"]):
        log(f"bench: {args.workload} needs {cell['chips']} chips, JAX sees "
            f"{len(devs)}")
        return 2
    line = run_cell(spec, args.workload, args.seed, args.seconds,
                    bool(args.trace))
    for k, c in line["checks"].items():
        log(f"check {k}={c['value']} limit={c['limit']}")
    print(json.dumps(line))
    return 0


if __name__ == "__main__":
    sys.exit(main())
