"""The four-chip Mugshot cell's configuration through the benchmark's
``run_cell``, on four CPU host devices at a small size.

``mugshot-schema-4m-mesh4`` carries ``mesh`` 4, so the run hands one
four-device partition mesh to every query of the reads mix.  A traced
run at ``bench/tests/conftest.small``'s size must read ``correct`` true,
report the mesh in ``info``, dispatch every chain and every fuzzy
T-occurrence stacked on the mesh with no fallback, and give the new
cell's per-layer metrics a number.  A fresh interpreter, since JAX fixes
its device count when it starts.
"""

import json
import os
import pathlib
import subprocess
import sys

import pytest

ROOT = pathlib.Path(__file__).resolve().parents[1]
SEED = 2 ** 31 + 8191
CELL = "schema-mesh4-indexed-reads"


def _probe() -> dict:
    sys.path[:0] = [str(ROOT), str(ROOT / "bench" / "tests")]
    from bench import run
    from conftest import small

    class Cell(run.Cell):
        def finish(self):
            line = super().finish()
            line["obs"] = {k: self.obs1.get(k, 0) - self.obs0.get(k, 0)
                           for k in ("mesh.tocc_dispatches",
                                     "mesh.stack_misses",
                                     "mesh.stack_hits")}
            line["fuzzy_requests"] = sum(
                1 for r in self.requests
                if r.req["template"] == "name_fuzzy")
            return line

    spec = run.load_spec()
    cell = next(w for w in spec["workloads"] if w["name"] == CELL)
    cfg = run.load_config(cell["config"])
    line = run.run_cell(spec, CELL, SEED, 3.0, True, overrides=small(cfg),
                        cell_cls=Cell)
    return {k: line[k] for k in ("correct", "checks", "info", "metrics",
                                 "obs", "fuzzy_requests")} | {
        "chips": cell["chips"], "mesh": cfg.get("mesh"),
        "partitions": cfg["partitions"]}


@pytest.fixture(scope="module")
def line(tmp_path_factory):
    path = tmp_path_factory.mktemp("bench_mesh") / "line.json"
    code = ("import json, sys; import test_bench_mesh as m; "
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


def test_cell_is_the_schema_deployment_on_four_chips(line):
    assert (line["chips"], line["mesh"], line["partitions"]) == (4, 4, 16)


def test_mesh_cell_is_correct(line):
    assert line["correct"], line["checks"]
    assert all(c["value"] == 0 for c in line["checks"].values())


def test_mesh_cell_runs_stacked_with_no_fallback(line):
    mesh = line["info"]["mesh"]
    assert mesh["devices"] == 4
    assert mesh["spmd_dispatches"] > 0
    assert mesh["spmd_fallbacks"] == 0


def test_every_fuzzy_request_is_one_stacked_t_occurrence(line):
    assert line["obs"]["mesh.tocc_dispatches"] > 0
    assert line["obs"]["mesh.tocc_dispatches"] == line["fuzzy_requests"]
    # warm queries find their stacks: nothing is stacked anew
    assert line["obs"]["mesh.stack_misses"] == 0
    assert line["obs"]["mesh.stack_hits"] > 0


@pytest.mark.parametrize("name", ["chain_stack_ms.reads", "tocc_ms.reads",
                                  "mesh_fallback_share.reads"])
def test_mesh_cell_metrics_read(line, name):
    assert name in line["metrics"]
    v = line["metrics"][name]["value"]
    if name == "mesh_fallback_share.reads":
        assert v == 0
    else:
        assert v > 0
