"""Compile the chip's main path for a described TPU v5e, without the chip.

The TPU compiler is installed here even where no TPU is attached, so these
tests lower and compile the programs ``chip_smoke.py`` runs, at its pool
size, and refuse what the chip's compiler would refuse: a Pallas kernel
Mosaic cannot lower, a pool that does not fit 16 GB, a donation XLA drops.
Nothing runs; results are the business of the CPU parity tests.
"""
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.core.api import REPAIR_CAP, _jit_write_phase
from repro.core.cache import IndexCache, _jit_cached_lookup
from repro.core.tree import TreeConfig, empty_state
from repro.core.write import RepairQueue
from repro.kernels.leaf_search.kernel import leaf_search

#: the pool chip_smoke.py loads 10^8 records into
SMOKE_CFG = TreeConfig(n_ms=4, nodes_per_ms=1 << 22, fanout=16,
                       n_locks_per_ms=131072, max_height=8, n_cs=8)
HBM_BYTES = 16 * 10**9        # one TPU v5e chip


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    # a compile for a described chip is written to the persistent cache
    # but cannot be read back without one: keep the cache off meanwhile
    from jax.experimental.compilation_cache import compilation_cache as cc
    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", prev)
    cc.reset_cache()


def _on(sharding, tree):
    return jax.tree_util.tree_map(
        lambda s: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=sharding),
        tree)


def _nbytes(tree):
    return sum(s.size * s.dtype.itemsize
               for s in jax.tree_util.tree_leaves(tree))


@pytest.mark.parametrize("b", [256, 4096])
def test_leaf_search_compiles(one_chip, b):
    f = SMOKE_CFG.fanout
    S = jax.ShapeDtypeStruct
    args = _on(one_chip, [S((b,), jnp.int32), S((b, f), jnp.int32),
                          S((b, f), jnp.int32), S((b, f), jnp.uint8),
                          S((b, f), jnp.uint8), S((b,), jnp.int32),
                          S((b,), jnp.int32), S((b,), jnp.int32)])
    compiled = jax.jit(lambda *a: leaf_search(*a)).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()


def test_cached_lookup_compiles_with_the_kernel(one_chip):
    cfg = SMOKE_CFG
    st = _on(one_chip, jax.eval_shape(lambda: empty_state(cfg)))
    rows = IndexCache(cfg).capacity_rows        # the default 64 MiB budget
    S = jax.ShapeDtypeStruct
    image = _on(one_chip, dict(
        rows=S((rows,), jnp.int32), keys=S((rows, cfg.fanout), jnp.int32),
        vals=S((rows, cfg.fanout), jnp.int32), level=S((rows,), jnp.int8),
        valid=S((rows,), jnp.bool_), fnv=S((rows,), jnp.uint8),
        root=S((), jnp.int32)))
    q = _on(one_chip, S((4096,), jnp.int32))
    compiled = _jit_cached_lookup.lower(cfg, st, image, q, 4,
                                        "pallas").compile()
    assert "tpu_custom_call" in compiled.as_text()


def test_write_phase_fits_and_donates(one_chip):
    cfg = SMOKE_CFG
    st = _on(one_chip, jax.eval_shape(lambda: empty_state(cfg)))
    repair = _on(one_chip, jax.eval_shape(
        lambda: RepairQueue.empty(REPAIR_CAP)))
    b = 2048
    S = jax.ShapeDtypeStruct
    lanes = _on(one_chip, [S((b,), jnp.int32), S((b,), jnp.int32),
                           S((b,), jnp.bool_), S((b,), jnp.bool_),
                           S((b,), jnp.int32)])
    compiled = _jit_write_phase.lower(cfg, st, *lanes, repair).compile()
    mem = compiled.memory_analysis()
    peak = (mem.argument_size_in_bytes + mem.output_size_in_bytes
            - mem.alias_size_in_bytes + mem.temp_size_in_bytes)
    assert peak < HBM_BYTES, peak
    # the pool and the repair queue are updated in place, not copied (the
    # chip pads small buffers, so the aliased bytes may exceed the
    # logical size, but only the lane inputs stay unaliased)
    assert mem.alias_size_in_bytes >= _nbytes(st) + _nbytes(repair)
    assert mem.argument_size_in_bytes - mem.alias_size_in_bytes \
        < 2 * _nbytes(lanes), mem
