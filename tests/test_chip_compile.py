"""Ahead-of-time compiles of the main path for a TPU v5e chip.

Each program is compiled for one device of a ``v5e:2x2`` topology that is
described, not attached, at the widths of the paper's SIFT1M deployment
(``ivfflat_sift1m(1.0)``: 4,000 lists, T_m = 1,024, 128-d f32 rows).
Nothing runs: a passing compile says the TPU compiler accepts the program
and that it fits the chip's memory, nothing about results or time.

The Pallas kernels are called with ``interpret=False`` directly:
``repro.kernels.ops`` picks interpret mode from ``jax.default_backend()``,
which is the CPU here even while compiling for the TPU.
"""

import functools

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.configs.anns import ivfflat_sift1m
from repro.core.block_pool import init_state
from repro.core.insert import make_insert_fn
from repro.core.mutate import make_delete_fn
from repro.core.runtime import pack_answers
from repro.core.search import make_search_fn
from repro.kernels.ivf_scan import coarse_topk, ivf_block_scan, ivf_block_topk

Q, B, NPROBE, K = 16, 128, 32, 10
HBM_BYTES = 16 * 10**9  # one v5e chip


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies

    try:
        return topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2"
        )
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    """One device of the described topology, with the persistent
    compilation cache off: its entries could not be read back without a
    chip, and every later compile would warn."""
    from jax.experimental.compilation_cache import compilation_cache as cc

    enabled = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", enabled)
    cc.reset_cache()


@pytest.fixture(scope="module")
def pool_cfg():
    return ivfflat_sift1m(1.0).pool_config()


@pytest.fixture(scope="module")
def spec(one_chip):
    def make(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    return make


@pytest.fixture(scope="module")
def state(pool_cfg, spec):
    """Shapes of the whole ``IVFState`` on the described chip."""
    shapes = jax.eval_shape(
        functools.partial(init_state, pool_cfg),
        jax.ShapeDtypeStruct((pool_cfg.n_clusters, pool_cfg.dim),
                             jnp.float32),
    )
    return jax.tree.map(lambda s: spec(s.shape, s.dtype), shapes)


def _compile(fn, *args):
    compiled = jax.jit(fn).lower(*args).compile()
    mem = compiled.memory_analysis()
    assert mem.argument_size_in_bytes + mem.temp_size_in_bytes < HBM_BYTES
    return compiled


def _n_kernels(compiled) -> int:
    return compiled.as_text().count('custom_call_target="tpu_custom_call"')


def test_ivf_block_scan_compiles(pool_cfg, spec):
    pc = pool_cfg
    compiled = _compile(
        lambda q, pool, ids: ivf_block_scan(q, pool, ids, interpret=False),
        spec((Q, pc.dim), jnp.float32),
        spec(pc.payload_shape(), jnp.float32),
        spec((Q * NPROBE,), jnp.int32),
    )
    assert _n_kernels(compiled) == 1


def test_block_table_search_step_compiles(pool_cfg, spec, state):
    step = make_search_fn(pool_cfg, nprobe=NPROBE, k=K, path="block_table",
                          chain_budget=1)
    compiled = _compile(step, state, spec((Q, pool_cfg.dim), jnp.float32))
    assert _n_kernels(compiled) == 0  # XLA gathers and matmuls only


def test_packed_search_answers_compile(pool_cfg, spec, state):
    """The runtime's search program ends in ``pack_answers``: one
    ``int32[Q, 2k]`` output, fetched in one transfer."""
    step = make_search_fn(pool_cfg, nprobe=NPROBE, k=K, path="block_table",
                          chain_budget=1)
    compiled = _compile(lambda st, q: pack_answers(*step(st, q)), state,
                        spec((Q, pool_cfg.dim), jnp.float32))
    (out,) = jax.tree.leaves(compiled.out_info)
    assert out.shape == (Q, 2 * K) and out.dtype == jnp.int32


def test_insert_step_compiles(pool_cfg, spec, state):
    _compile(
        make_insert_fn(pool_cfg), state,
        spec((B, pool_cfg.dim), jnp.float32), spec((B,), jnp.int32),
        spec((B,), jnp.bool_),
    )


def test_delete_step_compiles(pool_cfg, spec, state):
    _compile(make_delete_fn(pool_cfg), state, spec((B,), jnp.int32),
             spec((B,), jnp.bool_))


@pytest.mark.xfail(
    strict=True, raises=NotImplementedError,
    reason="the in-kernel top-nprobe merge uses jax.lax.sort, which the "
           "Pallas TPU lowering does not implement",
)
def test_coarse_topk_compiles(pool_cfg, spec):
    _compile(
        lambda q, c: coarse_topk(q, c, nprobe=NPROBE, interpret=False),
        spec((Q, pool_cfg.dim), jnp.float32),
        spec((pool_cfg.n_clusters, pool_cfg.dim), jnp.float32),
    )


@pytest.mark.xfail(
    strict=True, raises=ValueError,
    reason="the (1, T) blocks of the pool_ids / pool_live side tables are "
           "not (8, 128)-aligned; behind them, the in-kernel top-K' merge "
           "uses jax.lax.sort, which the Pallas TPU lowering does not "
           "implement",
)
def test_ivf_block_topk_f32_compiles(pool_cfg, spec):
    pc = pool_cfg
    c = Q * NPROBE
    _compile(
        lambda *a: ivf_block_topk(*a, kprime=128, interpret=False),
        spec((Q, pc.dim), jnp.float32),
        spec(pc.payload_shape(), jnp.float32),
        spec((c,), jnp.int32), spec((c,), jnp.int32),
        spec((pc.n_blocks, pc.block_size), jnp.int32),
        spec((pc.n_blocks, pc.block_size), jnp.uint8),
        spec((Q, NPROBE), jnp.int32),
    )
