"""End-to-end system behaviour: multi-device collectives (subprocess with a
forced device count), dry-run machinery smoke, and the roofline HLO parser."""

import json
import os
import subprocess
import sys
import textwrap
from pathlib import Path

import pytest

REPO = Path(__file__).resolve().parents[1]


def _run_in_subprocess(code: str, devices: int = 8) -> str:
    """Run code in a fresh python with N forced host devices (the only way
    to test collectives: jax locks the device count at first init)."""
    env = dict(os.environ)
    env["XLA_FLAGS"] = f"--xla_force_host_platform_device_count={devices}"
    env["PYTHONPATH"] = str(REPO / "src")
    out = subprocess.run([sys.executable, "-c", code], env=env,
                         capture_output=True, text=True, timeout=600)
    assert out.returncode == 0, out.stderr[-3000:]
    return out.stdout


def test_connector_collective_twins():
    """The Hyracks connector library lowers to the expected collectives and
    computes the right values under shard_map."""
    code = textwrap.dedent("""
        import jax, jax.numpy as jnp, numpy as np
        from jax.sharding import Mesh, PartitionSpec as P
        from jax import shard_map
        from repro.runtime import collectives as C

        from repro.runtime.mesh import make_mesh
        mesh = make_mesh((4,), ("data",))
        x = jnp.arange(8.0).reshape(4, 2)

        rep = shard_map(lambda x: C.replicate(x, "data"), mesh=mesh,
                        in_specs=P("data"), out_specs=P("data"))(x)
        assert rep.shape == (16, 2)

        tot = shard_map(lambda x: C.hierarchical_psum(x, ("data",)),
                        mesh=mesh, in_specs=P("data"),
                        out_specs=P("data"))(x)
        np.testing.assert_allclose(np.asarray(tot)[:1],
                                   np.asarray(x).reshape(4,1,2).sum(0))

        cp = shard_map(lambda x: C.compressed_psum(x, "data"), mesh=mesh,
                       in_specs=P("data"), out_specs=P("data"))(x)
        np.testing.assert_allclose(np.asarray(cp)[:1],
                                   np.asarray(x).reshape(4,1,2).sum(0),
                                   rtol=0.05, atol=0.05)
        print("COLLECTIVES-OK")
    """)
    assert "COLLECTIVES-OK" in _run_in_subprocess(code, devices=4)


def test_distributed_logsumexp_merge():
    """Context-parallel decode merge == local attention (the distributed LSM
    component merge)."""
    code = textwrap.dedent("""
        import jax, jax.numpy as jnp, numpy as np
        from jax.sharding import PartitionSpec as P
        from jax import shard_map
        from repro.runtime.collectives import distributed_logsumexp_merge
        from repro.kernels import ref as kref

        from repro.runtime.mesh import make_mesh
        mesh = make_mesh((4,), ("data",))
        rng = np.random.default_rng(0)
        B, H, hd, S = 2, 4, 16, 64
        q = jnp.asarray(rng.normal(size=(B, H, hd)), jnp.float32)
        k = jnp.asarray(rng.normal(size=(B, S, 1, hd)), jnp.float32)
        v = jnp.asarray(rng.normal(size=(B, S, 1, hd)), jnp.float32)

        def shard_fn(q, k, v):
            acc, m, l = kref.decode_partial_ref(q, k, v, k.shape[1])
            return distributed_logsumexp_merge(acc, m, l, "data")

        got = shard_map(shard_fn, mesh=mesh,
                        in_specs=(P(), P(None, "data"), P(None, "data")),
                        out_specs=P())(q, k, v)
        want = kref.flash_attention_ref(q[:, None], k, v,
                                        causal=False)[:, 0]
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   atol=2e-5, rtol=2e-5)
        print("MERGE-OK")
    """)
    assert "MERGE-OK" in _run_in_subprocess(code, devices=4)


def test_elastic_checkpoint_restore_across_meshes():
    """Save sharded on 8 devices, restore onto a 2x4 mesh — elastic
    scaling."""
    code = textwrap.dedent("""
        import tempfile, jax, jax.numpy as jnp, numpy as np
        from jax.sharding import NamedSharding, PartitionSpec as P
        from repro.checkpoint.manager import CheckpointManager

        from repro.runtime.mesh import make_mesh
        mesh8 = make_mesh((8,), ("data",))
        mesh2 = make_mesh((2, 4), ("data", "model"))
        w = jnp.arange(64.0).reshape(8, 8)
        w8 = jax.device_put(w, NamedSharding(mesh8, P("data")))
        with tempfile.TemporaryDirectory() as d:
            cm = CheckpointManager(d)
            cm.save(1, {"w": w8}, extra={})
            sh2 = {"w": NamedSharding(mesh2, P("data", "model"))}
            step, state, _ = cm.load_latest(shardings=sh2)
            assert state["w"].sharding.spec == P("data", "model")
            np.testing.assert_array_equal(np.asarray(state["w"]),
                                          np.asarray(w))
        print("ELASTIC-OK")
    """)
    assert "ELASTIC-OK" in _run_in_subprocess(code, devices=8)


def test_dryrun_machinery_on_reduced_mesh():
    """input_specs + make_step lower/compile on a small forced mesh for one
    train and one decode cell (fast proxy for the 512-dev run)."""
    code = textwrap.dedent("""
        import dataclasses, jax
        from repro.configs.base import SHAPES, ShapeConfig
        from repro.configs.registry import get_config
        from repro.configs.base import reduced
        from repro.launch.specs import input_specs, make_step, pick_rules

        from repro.runtime.mesh import make_mesh
        mesh = make_mesh((2, 2), ("data", "model"))
        for arch, shape_name in [("olmoe-1b-7b", "train_4k"),
                                 ("jamba-v0.1-52b", "decode_32k")]:
            cfg = reduced(get_config(arch))
            s = SHAPES[shape_name]
            shape = ShapeConfig(s.name, s.kind, 64, 4)
            rules = pick_rules(cfg, shape, model_axis=2)
            step, donate = make_step(cfg, shape, rules)
            args = input_specs(cfg, shape, mesh, rules)
            with mesh:
                c = jax.jit(step, donate_argnums=donate).lower(*args) \
                    .compile()
                ma = c.memory_analysis()
                peak = getattr(ma, "peak_memory_in_bytes", None)
                if peak is None:  # jax 0.4.x stats have no peak field
                    peak = (ma.temp_size_in_bytes + ma.argument_size_in_bytes
                            + ma.output_size_in_bytes)
                assert peak > 0
                ca = c.cost_analysis()
                if isinstance(ca, (list, tuple)):  # jax 0.4.x: per-device
                    ca = ca[0]
                assert "flops" in ca
        print("DRYRUN-OK")
    """)
    assert "DRYRUN-OK" in _run_in_subprocess(code, devices=4)


def test_hlo_collective_parser():
    from repro.roofline.analysis import collective_bytes
    hlo = """
  %p = f32[16,128]{1,0} parameter(0)
  %add.5 = f32[16,128]{1,0} add(%p, %p)
  %all-reduce.1 = f32[16,128]{1,0} all-reduce(%add.5), replica_groups={}
  %ag = f32[64,128]{1,0} all-gather(%add.5), dimensions={0}
  %rs = f32[4,128]{1,0} reduce-scatter(%all-reduce.1), dimensions={0}
"""
    got = collective_bytes(hlo)
    assert got["all-reduce"] == 16 * 128 * 4
    assert got["all-gather"] == 16 * 128 * 4       # operand bytes
    assert got["reduce-scatter"] == 16 * 128 * 4
    assert got["total"] == 3 * 16 * 128 * 4


def test_roofline_report_terms():
    from repro.roofline.analysis import RooflineReport
    rep = RooflineReport("a", "s", "pod1", 256, hlo_flops=197e12,
                         hlo_bytes=819e9, coll_bytes=50e9,
                         model_flops_total=197e12 * 256 * 0.5)
    assert rep.compute_s == pytest.approx(1.0)
    assert rep.memory_s == pytest.approx(1.0)
    assert rep.collective_s == pytest.approx(1.0)
    assert rep.mfu == pytest.approx(0.5)
