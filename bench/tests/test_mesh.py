"""A configuration's ``mesh``: the run hands one partition mesh to every
program query, reports what the mesh did, and refuses a layout its cell
cannot run before any set-up."""

import json
import os
import subprocess
import sys
import textwrap

import pytest

from bench import run
from conftest import ROOT

SEED = 7 ** 11


def test_mesh_run_takes_the_spmd_path():
    """The reads mix through ``run_cell`` over a 4-device CPU mesh reads
    ``correct`` true with SPMD dispatches and no fallback; the same seed
    without the mesh reads ``correct`` true with no mesh.  A fresh
    process, since JAX fixes its device count when it starts."""
    code = textwrap.dedent(f"""
        import copy, json, sys
        sys.path[:0] = [{ROOT!r}, {os.path.join(ROOT, "src")!r},
                        {os.path.join(ROOT, "bench", "tests")!r}]
        from bench import run
        from conftest import small
        spec = copy.deepcopy(run.load_spec())
        spec["workloads"].append({{
            "name": "schema-indexed-reads-mesh4",
            "config": "mugshot-schema-4m", "traffic": "indexed-reads",
            "chips": 4, "why": "test only"}})
        cfg = run.load_config("mugshot-schema-4m")
        ov = dict(small({{**cfg, "partitions": 8}}), partitions=8)
        out = {{}}
        for tag, extra in (("mesh", {{"mesh": 4}}), ("loop", {{}})):
            line = run.run_cell(spec, "schema-indexed-reads-mesh4",
                                {SEED}, 3.0, False,
                                overrides={{**ov, **extra}})
            out[tag] = {{"correct": line["correct"],
                        "checks": line["checks"],
                        "mesh": line["info"]["mesh"]}}
        print("RESULT " + json.dumps(out))
    """)
    env = dict(os.environ,
               XLA_FLAGS="--xla_force_host_platform_device_count=4",
               JAX_PLATFORMS="cpu")
    proc = subprocess.run([sys.executable, "-c", code], env=env, cwd=ROOT,
                          capture_output=True, text=True, timeout=600)
    assert proc.returncode == 0, proc.stderr[-3000:]
    out = json.loads(proc.stdout.split("RESULT ", 1)[1])
    mesh, loop = out["mesh"], out["loop"]
    assert mesh["correct"], mesh["checks"]
    assert mesh["mesh"]["devices"] == 4
    assert mesh["mesh"]["spmd_dispatches"] > 0
    assert mesh["mesh"]["spmd_partitions"] > 0
    assert mesh["mesh"]["spmd_fallbacks"] == 0
    assert loop["correct"], loop["checks"]
    assert loop["mesh"] == {"devices": 0, "spmd_dispatches": 0,
                            "spmd_partitions": 0, "spmd_fallbacks": 0}


CELL4 = {"name": "t", "config": "mugshot-schema-4m",
         "traffic": "indexed-reads", "chips": 4}


@pytest.mark.parametrize("mesh,partitions,chips,devices", [
    (None, 8, 1, 0), (2, 8, 4, 2), (4, 8, 4, 4), (4, 16, 4, 4)])
def test_layout_is_taken(mesh, partitions, chips, devices):
    cfg = {"partitions": partitions}
    if mesh is not None:
        cfg["mesh"] = mesh
    assert run.mesh_devices(cfg, {**CELL4, "chips": chips}) == devices


class NoSetup:
    def __init__(self, *a, **k):
        raise AssertionError("set-up reached")


@pytest.mark.parametrize("mesh", [0, 1, 3, "4", 8, 4.0, True])
def test_bad_layout_is_refused_before_setup(spec, monkeypatch, mesh):
    monkeypatch.setattr(run, "Deployment", NoSetup)
    spec = dict(spec, workloads=spec["workloads"] + [CELL4])
    with pytest.raises(ValueError, match="'mesh'"):
        run.run_cell(spec, "t", SEED, 1.0, False,
                     overrides={"mesh": mesh, "partitions": 8})


def test_bad_layout_exits_2_from_main(monkeypatch, capsys):
    """The one-chip reads cell with a 2-chip mesh: exit 2 and no result
    line, before JAX is asked for a device."""
    cfg = dict(run.load_config("mugshot-schema-4m"), mesh=2)
    monkeypatch.setattr(run, "load_config", lambda name: cfg)
    monkeypatch.setattr(run, "Deployment", NoSetup)
    rc = run.main(["--workload", "schema-indexed-reads", "--seed", "1",
                   "--seconds", "1"])
    out = capsys.readouterr()
    assert rc == 2
    assert out.out == ""
    assert "'mesh'" in out.err


def test_no_layout_runs_the_loop(spec, monkeypatch):
    """A configuration without ``mesh`` calls the program's entry with
    ``mesh=None``: the call the cells made before the key existed."""
    from repro.storage import query
    calls = []

    class Stats:
        fallback_reasons = []
        kernel_retraces = 0

    class Ex:
        stats = Stats()

    def fake(plan, datasets, **kw):
        calls.append(kw)
        return [], Ex()
    monkeypatch.setattr(query, "run_query", fake)
    cell = next(w for w in spec["workloads"]
                if w["name"] == "schema-indexed-reads")
    c = run.Cell(spec, cell, SEED, 1.0, False)
    c._execute(run.Request({"template": "t", "check": False}, 0.0), None, {})
    assert calls == [{"vectorize": True, "snapshot": True, "mesh": None}]
