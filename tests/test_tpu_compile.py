"""Compile the chip's main path for a described TPU v5e, without the chip.

The TPU compiler is installed here even where no TPU is attached, so these
tests lower and compile the programs ``chip_smoke.py`` runs, at its pool
size, and refuse what the chip's compiler would refuse: a Pallas kernel
Mosaic cannot lower, a pool that does not fit 16 GB, a donation XLA drops.
Nothing runs; results are the business of the CPU parity tests.
"""
import os
import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.core.api import REPAIR_CAP, _jit_write_phase
from repro.core.cache import IndexCache, _jit_cached_lookup
from repro.core.tree import TreeConfig, empty_state
from repro.core.write import RepairQueue
from repro.kernels.leaf_search.kernel import leaf_search
from repro.kernels.pool_rows.kernel import stored_transposed

#: the pool chip_smoke.py loads 10^8 records into
SMOKE_CFG = TreeConfig(n_ms=4, nodes_per_ms=1 << 22, fanout=16,
                       n_locks_per_ms=131072, max_height=8, n_cs=8)
#: the benchmark's pool (bench/configs): 1 KB nodes of 58 slots
BENCH_CFG = TreeConfig(n_ms=4, nodes_per_ms=1 << 20, fanout=58,
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


def _cache_image(cfg, sharding):
    rows = IndexCache(cfg).capacity_rows        # the default 64 MiB budget
    S = jax.ShapeDtypeStruct
    return _on(sharding, dict(
        rows=S((rows,), jnp.int32), keys=S((rows, cfg.fanout), jnp.int32),
        vals=S((rows, cfg.fanout), jnp.int32), level=S((rows,), jnp.int8),
        valid=S((rows,), jnp.bool_), fnv=S((rows,), jnp.uint8),
        root=S((), jnp.int32)))


def _main_computation(hlo: str) -> list:
    """The instructions of the entry computation and of every computation
    it calls (fusions, loops, reductions), but not those of a
    conditional's branches."""
    comps, name, entry = {}, None, None
    for line in hlo.splitlines():
        head = re.match(r"(ENTRY )?%(\S+) .*\{$", line)
        if head:
            name = head.group(2)
            entry = name if head.group(1) else entry
            comps[name] = []
        elif line.startswith("}"):
            name = None
        elif name:
            comps[name].append(line)
    seen, todo = set(), [entry]
    while todo:
        c = todo.pop()
        if c not in seen:
            seen.add(c)
            for line in comps[c]:
                todo += re.findall(r"(?:calls|to_apply|body|condition)"
                                   r"=%([\w.\-]+)", line)
    return [line for c in seen for line in comps[c]]


_MOVE = re.compile(r"= \w+\[(\d+),(\d+)\]\{[^}]*\} (?:copy|transpose)\(")


@pytest.mark.parametrize("f", [16, 58, 127, 128, 200])
def test_pool_layout_is_decided_by_its_shape(one_chip, f):
    """The compiler stores an [N, F] column transposed exactly where
    ``stored_transposed`` says, so the pool-row read takes the path that
    reads the column as it is stored."""
    col = _on(one_chip, jax.ShapeDtypeStruct((1 << 20, f), jnp.int32))
    idx = _on(one_chip, jax.ShapeDtypeStruct((512,), jnp.int32))
    text = jax.jit(lambda c, i: c[i]).lower(col, idx).compile().as_text()
    param = re.search(r"= s32\[%d,%d\]\{([\d,]+)" % (1 << 20, f),
                      text[text.index("ENTRY"):])
    assert (param.group(1) == "0,1") == stored_transposed((1 << 20, f))


@pytest.mark.parametrize("cfg", [BENCH_CFG, SMOKE_CFG], ids=["f58", "f16"])
def test_cached_lookup_reads_the_pool_in_place(one_chip, cfg):
    """No whole-pool-column copy or transpose in the cached lookup's main
    computation: the leaf rows are read from the pool in its own layout.
    (The fallback retraversal's branch may still copy.)"""
    st = _on(one_chip, jax.eval_shape(lambda: empty_state(cfg)))
    q = _on(one_chip, jax.ShapeDtypeStruct((512,), jnp.int32))
    text = _jit_cached_lookup.lower(cfg, st, _cache_image(cfg, one_chip), q,
                                    4, "pallas").compile().as_text()
    pool = sorted((cfg.n_nodes, cfg.fanout))
    moved = [line.strip()[:120] for line in _main_computation(text)
             if (m := _MOVE.search(line))
             and sorted(map(int, m.groups())) == pool]
    assert not moved, moved
    assert "tpu_custom_call" in text


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
    q = _on(one_chip, jax.ShapeDtypeStruct((4096,), jnp.int32))
    compiled = _jit_cached_lookup.lower(cfg, st, _cache_image(cfg, one_chip),
                                        q, 4, "pallas").compile()
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
