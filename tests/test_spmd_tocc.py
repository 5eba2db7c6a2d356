"""The stacked ngram T-occurrence over a four-device partition mesh.

Under an active mesh the fuzzy index chain counts every partition's
query-gram hits in one ``shard_map`` dispatch
(``runtime/spmd.batched_t_occurrence``) in place of one
``ngram_t_occurrence`` call per partition.  On seeded Mugshot users over
16 partitions, each with flushed components and a memtable tail, its
candidate masks must equal the loop's bit for bit, the fuzzy select's
rows must equal the plain reference's (``bench/reference.py``), and a
query the stacked path declines must count a fallback and still answer
as the loop does.

The probe runs in a fresh interpreter with
``XLA_FLAGS=--xla_force_host_platform_device_count=4`` (JAX fixes its
device count when it starts, and the test run sets no such flag); the
tests below read its report.
"""

import json
import os
import pathlib
import subprocess
import sys

import pytest

ROOT = pathlib.Path(__file__).resolve().parents[1]
SEED = 2 ** 31 + 4099
QUERIES = (("User Number 1234", 2), ("User Number 77", 1),
           ("User Number 16000", 2), ("Usr Numbr 4321", 2))


def _delta(s0, s1, key):
    return s1.get(key, 0) - s0.get(key, 0)


def _probe() -> dict:
    """Build the deployment, run the checks, return what they saw."""
    import numpy as np

    from bench import gen, reference, run, traffic
    from repro import obs
    from repro.runtime import spmd
    from repro.storage.query import run_query

    assert len(__import__("jax").devices()) == 4
    cfg = run.load_config("mugshot-schema-4m")
    n_users = (1 << 14) + 77         # not a multiple of any flush: tails
    ds_cfg = json.loads(json.dumps(cfg["datasets"]))
    ds_cfg["MugshotUsers"]["flush_threshold"] = 96
    ds_cfg["MugshotMessages"]["flush_threshold"] = 64
    cfg.update(users=n_users, messages=1 << 12, datasets=ds_cfg)
    dep = run.Deployment(cfg, SEED)
    dep.build()
    users = dep.datasets["MugshotUsers"]
    out: dict = {"tiers": [], "masks_equal": [], "rows_equal": [],
                 "mesh_tocc": [], "loop_tocc": [], "stacked_kernel": [],
                 "candidates": []}
    for i in range(users.num_partitions):
        view = users._view(i)
        out["tiers"].append([len(view.memtable),
                             sum(1 for c in view.components if c.size)])

    ref = reference.Reference(dep.users, dep.msgs)
    t = next(x for x in traffic.load_mix("indexed-reads")["templates"]
             if x["kind"] == "fuzzy_ed")
    dom = traffic.domains(n_users)
    for q, ed in QUERIES:
        spec = ("name", "ed", q, ed)
        loop = [users.ngram_candidate_mask(i, "name", spec)
                for i in range(users.num_partitions)]
        with spmd.use_partition_mesh(4):
            s0 = obs.snapshot()
            masks = users.ngram_candidate_masks("name", spec)
            s1 = obs.snapshot()
        out["masks_equal"].append(
            len(masks) == len(loop)
            and all(a.dtype == b.dtype and np.array_equal(a, b)
                    for a, b in zip(masks, loop)))
        out["candidates"].append(int(sum(m.sum() for m in loop)))
        out["stacked_kernel"].append(
            [_delta(s0, s1, "mesh.tocc_dispatches"),
             _delta(s0, s1, "kernel.spmd_t_occurrence.dispatches"),
             _delta(s0, s1, "kernel.ngram_t_occurrence.dispatches"),
             _delta(s0, s1, "mesh.spmd_fallbacks")])

        req = {"template": t["name"], "kind": "fuzzy_ed", "query": q,
               "check": True}
        tq = dict(t, ed=ed)
        plan = traffic.plan(req, tq, dom)
        want = ref.answer(req, tq)
        s0 = obs.snapshot()
        rows, _ = run_query(plan, dep.datasets, vectorize=True,
                            snapshot=True, mesh=4)
        s1 = obs.snapshot()
        loop_rows, _ = run_query(plan, dep.datasets, vectorize=True,
                                 snapshot=True)
        s2 = obs.snapshot()
        out["rows_equal"].append(
            [reference.canon_rows(rows) == want,
             reference.canon_rows(loop_rows) == want, len(want)])
        out["mesh_tocc"].append(
            [_delta(s0, s1, "mesh.tocc_dispatches"),
             _delta(s0, s1, "kernel.ngram_t_occurrence.dispatches")])
        out["loop_tocc"].append(
            [_delta(s1, s2, "mesh.tocc_dispatches"),
             _delta(s1, s2, "kernel.ngram_t_occurrence.dispatches")])

    # a name only one partition holds: its grams hit one partition, so
    # the stacked path declines and the loop answers
    users.insert({**gen.user_records(gen.users(1, SEED))[0],
                  "id": 10 ** 9, "name": "Qwixzy Jorvak"})
    spec = ("name", "ed", "Qwixzy Jorvak", 1)
    loop = [users.ngram_candidate_mask(i, "name", spec)
            for i in range(users.num_partitions)]
    with spmd.use_partition_mesh(4):
        s0 = obs.snapshot()
        masks = users.ngram_candidate_masks("name", spec)
        s1 = obs.snapshot()
    plan = traffic.plan({"template": t["name"], "kind": "fuzzy_ed",
                         "query": "Qwixzy Jorvak"}, dict(t, ed=1), dom)
    rows, _ = run_query(plan, dep.datasets, vectorize=True, snapshot=True,
                        mesh=4)
    out["declined"] = {
        "masks_equal": all(np.array_equal(a, b)
                           for a, b in zip(masks, loop)),
        "partitions_with_candidates": sum(int(m.any()) for m in loop),
        "fallbacks": _delta(s0, s1, "mesh.spmd_fallbacks"),
        "tocc_dispatches": _delta(s0, s1, "mesh.tocc_dispatches"),
        "rows": [r["id"] for r in rows]}
    return out


@pytest.fixture(scope="module")
def probe(tmp_path_factory):
    path = tmp_path_factory.mktemp("tocc") / "probe.json"
    code = ("import json, sys; import test_spmd_tocc as m; "
            "open(sys.argv[1], 'w').write(json.dumps(m._probe()))")
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    env["PYTHONPATH"] = os.pathsep.join(
        [str(ROOT / "tests"), str(ROOT / "src"), str(ROOT)]
        + ([env["PYTHONPATH"]] if env.get("PYTHONPATH") else []))
    proc = subprocess.run([sys.executable, "-c", code, str(path)], env=env,
                          cwd=str(ROOT), capture_output=True, text=True,
                          timeout=600)
    assert proc.returncode == 0, proc.stderr[-4000:]
    return json.loads(path.read_text())


def test_partitions_have_components_and_a_memtable_tail(probe):
    assert len(probe["tiers"]) == 16
    assert all(mem > 0 and comps >= 2 for mem, comps in probe["tiers"])


@pytest.mark.parametrize("q", range(len(QUERIES)))
def test_stacked_masks_equal_the_loop(probe, q):
    assert probe["candidates"][q] > 0
    assert probe["masks_equal"][q]


@pytest.mark.parametrize("q", range(len(QUERIES)))
def test_one_dispatch_replaces_the_loop(probe, q):
    tocc, spmd_kernel, loop_kernel, fallbacks = probe["stacked_kernel"][q]
    assert (tocc, spmd_kernel, loop_kernel, fallbacks) == (1, 1, 0, 0)
    # through run_query: one stacked dispatch under the mesh, none of
    # the loop's; without the mesh, the loop's per-partition kernels
    assert probe["mesh_tocc"][q] == [1, 0]
    assert probe["loop_tocc"][q][0] == 0
    assert probe["loop_tocc"][q][1] >= 16


@pytest.mark.parametrize("q", range(len(QUERIES)))
def test_mesh_rows_equal_the_reference(probe, q):
    mesh_ok, loop_ok, n = probe["rows_equal"][q]
    assert mesh_ok and loop_ok
    assert n > 0


def test_declined_query_falls_back_and_answers(probe):
    d = probe["declined"]
    assert d["partitions_with_candidates"] == 1
    assert d["masks_equal"]
    assert d["fallbacks"] == 1
    assert d["tocc_dispatches"] == 0
    assert d["rows"] == [10 ** 9]
