"""Compile-only checks of the device path for a described TPU v5e chip.

Interpret-mode tests prove the Pallas kernels' answers; they cannot see
what the TPU compiler refuses (unaligned lane reads, scatters, tiles that
break the (8, 128) layout).  Each test here lowers and compiles one
kernel of the main path at 2**20 rows for ``v5e:2x2`` with the compiler
that ships with libtpu — no chip attached, nothing runs.  The topology
is described inside a fixture (never at import), so every pytest worker
collects the same tests and only the one that runs this file loads
libtpu.
"""

import pytest

import jax
import jax.numpy as jnp
from jax.sharding import SingleDeviceSharding

from repro.columnar import plancache as PC
from repro.kernels import columnar_ops as K
from repro.kernels import fuzzy_ops as F

N = 1 << 20


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def spec(topo):
    one_chip = SingleDeviceSharding(topo.devices[0])

    def make(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)
    return make


def _compile(fn, *args, **static):
    with jax.enable_x64(True):
        compiled = fn.lower(*args, **static).compile()
    assert compiled.as_text()
    return compiled


def _has_kernel(compiled) -> bool:
    return "tpu_custom_call" in compiled.as_text()


def test_range_mask_kernel_compiles(spec):
    datas = (spec((N,), jnp.int64), spec((N,), jnp.int64))
    valids = (spec((N,), jnp.bool_), spec((N,), jnp.bool_))
    c = _compile(K._mask_pallas_core, datas, valids, spec((4,), jnp.int32))
    assert _has_kernel(c)


def test_fused_aggregate_kernel_compiles(spec):
    datas = (spec((N,), jnp.int64),)
    valids = (spec((N,), jnp.bool_),)
    aggs = (spec((N,), jnp.int64), spec((N,), jnp.int64))
    avs = (spec((N,), jnp.bool_), spec((N,), jnp.bool_))
    c = _compile(K._agg_pallas_core, datas, valids, spec((2,), jnp.int32),
                 aggs, avs)
    assert _has_kernel(c)


def test_sorted_intersect_kernel_compiles(spec):
    c = _compile(K._intersect_pallas_core, spec((N,), jnp.int64),
                 spec((K.SMEM_CAP,), jnp.int64))
    assert _has_kernel(c)


def test_edit_distance_kernel_compiles(spec):
    B, L, M = 1 << 14, 32, 32
    c = _compile(F._ed_pallas_core, spec((L, B), jnp.int32),
                 spec((1, B), jnp.int32), spec((M,), jnp.int32),
                 spec((2,), jnp.int32))
    assert _has_kernel(c)


def test_set_intersect_core_compiles(spec):
    """The Jaccard verifier's exact x64 core (per-pair searchsorted; it
    runs on the chip too) over 2**14 pairs."""
    P, S1, S2 = 1 << 14, 32, 32
    _compile(F._inter_core, spec((P, S1), jnp.int64), spec((P,), jnp.int64),
             spec((P, S2), jnp.int64))


def test_exact_cores_compile(spec):
    """The x64 jnp cores that take every input the Pallas kernels do not
    (int64 pks, datetimes, sums, large candidate sets) compile for the
    chip too.  ``_intersect_rank_core`` is left out: the LSM merge runs
    it only where ``use_pallas()`` is false, never on a TPU."""
    _compile(K._intersect_core, spec((N,), jnp.int64),
             spec((1 << 16,), jnp.int64))
    datas = (spec((N,), jnp.int64),)
    valids = (spec((N,), jnp.bool_),)
    b = (spec((), jnp.int64),)
    _compile(K._agg_core, datas, valids, b, b,
             (spec((N,), jnp.int64), spec((N,), jnp.float64)),
             (spec((N,), jnp.bool_), spec((N,), jnp.bool_)))


def test_ngram_t_occurrence_core_compiles(spec):
    """One tier's hit-segment gather + scatter-count, and the threshold
    pass, over a 2**20-row storage concat."""
    i64 = spec((), jnp.int64)
    cnt = spec((N + 1,), jnp.int32)
    seg = spec((64,), jnp.int64)
    _compile(F._tocc_tier_core, cnt, spec((N,), jnp.int64), seg, seg,
             i64, i64, N // 4)
    _compile(F._tocc_finish, cnt, spec((), jnp.int32))


def test_bitset_popcount_core_compiles(spec):
    """The FuzzyJoin verifier's AND + popcount core (XLA on every
    backend) over 2**16 record bitsets and 2**20 pairs."""
    _compile(F._popcount_inter_core, spec((1 << 16, 32), jnp.uint32),
             spec((N,), jnp.int64), spec((N,), jnp.int64))


def test_fused_chain_core_compiles(spec):
    """``plancache._chain_core``: the whole Figure-6 chain (btree probe ->
    per-tier scatter -> bitmap AND -> gather -> validate -> reduce) as
    one x64 program at 2**20 rows."""
    i64 = spec((), jnp.int64)
    tiers = ((spec((N // 2,), jnp.int64), spec((N // 8,), jnp.int64)),)
    bounds = (((i64, i64, i64), (i64, i64, i64)),)
    preds = ((spec((N,), jnp.int64), spec((N,), jnp.bool_), i64, i64),)
    aggs = ((spec((N,), jnp.int64), spec((N,), jnp.bool_)),)
    _compile(PC._chain_core, tiers, bounds, spec((N,), jnp.int64), i64,
             preds, aggs, total_p2=N, live_p2=N)
