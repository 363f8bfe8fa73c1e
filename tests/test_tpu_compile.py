"""Compile the main path's programs for a described TPU v5e (nothing runs).

XLA's TPU compiler is installed even where no chip is attached, so these
tests catch what interpret mode cannot: Mosaic refusing a kernel, tiling
errors, and programs that do not fit the chip's memory. The topology is
described inside a module fixture only (never at import): just one process
may load the TPU library, and the test runner's other workers must collect
the same tests without touching it.
"""
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
from jax.sharding import SingleDeviceSharding

from repro.core.dedup import FoldConfig, bitmap_tau
from repro.core.hnsw import abstract_state, hnsw_insert_batch, hnsw_search
from repro.core.sharded import make_sharded_dedup_step, sharded_init_program
from repro.kernels.bitmap_jaccard import bitmap_jaccard_matrix
from repro.kernels.minhash import minhash_kernel_signatures

# the index size chip_smoke.py runs, per chip (per shard on the 2x2 mesh):
# the programs must fit v5e's 16 GB HBM
CHIP_CAPACITY = 1 << 21
V5E_HBM_BYTES = 16 * 10**9
B = 128          # the service's largest micro-batch bucket


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    try:
        desc = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a described-chip compile is written to the persistent cache but can
    # never be read back without the chip: keep the cache out of it
    was_on = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield desc
    jax.config.update("jax_enable_compilation_cache", was_on)
    compilation_cache.reset_cache()


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _sds(shape, dtype, sharding):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _nbytes(tree):
    return sum(x.size * x.dtype.itemsize for x in jax.tree.leaves(tree))


def _report(what, compiled):
    """The compiler's memory figures for `compiled` (shown with -s)."""
    m = compiled.memory_analysis()
    print(f"{what}: arguments {m.argument_size_in_bytes} B, temp "
          f"{m.temp_size_in_bytes} B, output {m.output_size_in_bytes} B, "
          f"aliased {m.alias_size_in_bytes} B")
    return m


def _hnsw_args(sharding, capacity):
    hcfg = FoldConfig(capacity=capacity).hnsw()
    state = jax.tree.map(lambda s: _sds(s.shape, s.dtype, sharding),
                         abstract_state(hcfg))
    return hcfg, state


def test_minhash_kernel_compiles(one_chip):
    c = minhash_kernel_signatures.lower(
        _sds((B, 512), jnp.uint32, one_chip),
        _sds((112,), jnp.uint32, one_chip), interpret=False).compile()
    assert "tpu_custom_call" in c.as_text()


@pytest.mark.parametrize("cached", [True, False])
def test_bitmap_jaccard_kernel_compiles(one_chip, cached):
    W = FoldConfig().T // 32
    args = (_sds((B, W), jnp.uint32, one_chip),
            _sds((B, W), jnp.uint32, one_chip))
    if cached:
        args += (_sds((B,), jnp.int32, one_chip),
                 _sds((B,), jnp.int32, one_chip))
    c = bitmap_jaccard_matrix.lower(*args, cached=cached,
                                    interpret=False).compile()
    assert "tpu_custom_call" in c.as_text()


def test_hnsw_search_compiles_and_fits(one_chip):
    hcfg, state = _hnsw_args(one_chip, CHIP_CAPACITY)
    c = hnsw_search.lower(hcfg, state, _sds((B, hcfg.words), jnp.uint32,
                                            one_chip), k=4).compile()
    m = _report("hnsw_search", c)
    assert m.argument_size_in_bytes + m.temp_size_in_bytes < V5E_HBM_BYTES


def test_hnsw_insert_batch_compiles_and_fits(one_chip):
    hcfg, state = _hnsw_args(one_chip, CHIP_CAPACITY)
    row = lambda d: _sds((B,), d, one_chip)    # noqa: E731
    c = hnsw_insert_batch.lower(
        hcfg, state, _sds((B, hcfg.words), jnp.uint32, one_chip),
        row(jnp.int32), row(jnp.int32), row(jnp.bool_),
        _sds((B, 4), jnp.int32, one_chip), None).compile()
    m = _report("hnsw_insert_batch", c)
    # the state is donated: its buffers are reused for the output
    assert m.alias_size_in_bytes >= m.argument_size_in_bytes // 2
    assert m.argument_size_in_bytes + m.temp_size_in_bytes < V5E_HBM_BYTES


@pytest.fixture(scope="module")
def mesh_2x2(topo):
    mesh = Mesh(np.asarray(topo.devices).reshape(-1), ("data",))
    assert mesh.shape["data"] == 4
    return mesh


def test_sharded_init_builds_one_shard_per_device(mesh_2x2):
    """Each device holds one shard's state, never the stacked state (at
    2^21 slots per shard the stack is ~9.7 GB and does not fit one chip)."""
    hcfg = FoldConfig(capacity=CHIP_CAPACITY).hnsw()
    shard_bytes = _nbytes(abstract_state(hcfg))
    m = sharded_init_program(hcfg, mesh_2x2).lower().compile() \
        .memory_analysis()
    print(f"sharded_init per device: output {m.output_size_in_bytes} B, "
          f"one shard {shard_bytes} B")
    assert m.output_size_in_bytes < 1.1 * shard_bytes


def test_sharded_dedup_step_compiles_on_2x2(mesh_2x2):
    mesh, n = mesh_2x2, mesh_2x2.shape["data"]
    cfg = FoldConfig(capacity=CHIP_CAPACITY)      # per shard, as the smoke
    hcfg = cfg.hnsw()
    shard = NamedSharding(mesh, P("data"))
    states = jax.tree.map(
        lambda s: _sds((n,) + s.shape, s.dtype, shard), abstract_state(hcfg))
    step = jax.jit(make_sharded_dedup_step(
        hcfg, mesh, tau=bitmap_tau(cfg), k=cfg.k, axis="data", masked=True,
        reuse_search=True, free_slots=True))
    c = step.lower(states, _sds((B, hcfg.words), jnp.uint32, shard),
                   _sds((B,), jnp.int32, shard), _sds((B,), jnp.int32, shard),
                   _sds((B,), jnp.bool_, shard),
                   _sds((n, B // n), jnp.int32, shard)).compile()
    hlo = c.as_text()
    assert "all-gather" in hlo and "all-reduce" in hlo
    m = _report("hnsw_sharded fused step per device", c)
    assert m.argument_size_in_bytes + m.temp_size_in_bytes < V5E_HBM_BYTES
