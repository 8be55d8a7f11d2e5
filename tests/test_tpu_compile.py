"""Ahead-of-time compiles of the serving kernels for a described TPU v5e.

The TPU compiler is installed even where no chip is attached: it compiles
for a topology that is only described, and refuses what the chip would
refuse (block shapes off the (sublane, 128-lane) tiling, VMEM overuse, a
kernel that cannot be partitioned). Interpret-mode tests cannot see any of
that, so these compiles guard the main path's kernels at full qwen1.5-4b
widths (20 query / 20 kv heads, head_dim 128, bf16) on one described chip,
plus the shard_map'd decode kernels on a described 1x4 mesh.

Every call that loads the TPU library happens inside a fixture, after a
test has started: only one process may hold that library, and the test
runner imports this file in every worker. All compiles stay in this file
so that a single worker is the one that loads it.
"""
import json
import os
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
from jax.sharding import SingleDeviceSharding

from repro.configs import get_config
from repro.kernels.flash_attention.ops import paged_flash_prefill
from repro.kernels.paged_attention.ops import (fused_decode_attention,
                                               fused_decode_attention_sharded,
                                               paged_attention,
                                               paged_attention_sharded)

QWEN = get_config("qwen1.5-4b")
H, KH, D = QWEN.num_heads, QWEN.num_kv_heads, QWEN.head_dim
BF16 = jnp.bfloat16
B = 8                  # decode slots
MAX_LEN = 1024         # tokens per sequence
KT = 8                 # fused decode steps per call (tail rows)
CHUNK = 256            # prefill chunk
V5E_SCOPED_VMEM = 16 << 20     # v5e's default scoped-VMEM limit per kernel


@pytest.fixture(scope="module")
def no_compile_cache():
    """Compiles for a described chip are written to a persistent cache but
    cannot be read back without one: keep the cache off around them."""
    from jax.experimental.compilation_cache import compilation_cache as cc
    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", prev)
    cc.reset_cache()


@pytest.fixture(scope="module")
def topo(no_compile_cache):
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:                       # noqa: BLE001
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module")
def mesh4(topo):
    return Mesh(np.array(topo.devices).reshape(1, 4), ("data", "model"),
                axis_types=(jax.sharding.AxisType.Auto,) * 2)


def _spec(shape, sharding, dtype=BF16):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _scoped_vmem_bytes(hlo: str) -> list[int]:
    """Scoped VMEM each Pallas kernel in the compiled HLO was given."""
    sizes = []
    for line in hlo.splitlines():
        m = re.search(r'"used_scoped_memory_configs":(\[[^\]]*\])', line)
        if m and 'custom_call_target="tpu_custom_call"' in line:
            sizes.append(sum(int(c["size"]) for c in json.loads(m.group(1))))
    return sizes


def _assert_kernel_compiled(compiled):
    hlo = compiled.as_text()
    assert "tpu_custom_call" in hlo, "no Pallas kernel in the compiled HLO"
    vmem = _scoped_vmem_bytes(hlo)
    assert vmem and max(vmem) <= V5E_SCOPED_VMEM, vmem


def _decode_args(page, sharding, pool_sharding=None):
    pps = MAX_LEN // page
    pool = (B * pps + 1, KH, page, D)
    ps = pool_sharding or sharding
    return (_spec((B, H, D), sharding), _spec(pool, ps), _spec(pool, ps),
            _spec((B, pps), sharding, jnp.int32),
            _spec((B,), sharding, jnp.int32))


def _tail_args(sharding, tail_sharding=None):
    ts = tail_sharding or sharding
    return (_spec((B, KH, KT, D), ts), _spec((B, KH, KT, D), ts),
            _spec((B,), sharding, jnp.int32))


@pytest.mark.parametrize("page", [16, 128])
def test_paged_decode_compiles_for_v5e(one_chip, page):
    fn = jax.jit(lambda *a: paged_attention(*a, interpret=False))
    _assert_kernel_compiled(fn.lower(*_decode_args(page, one_chip)).compile())


@pytest.mark.parametrize("page", [16, 128])
def test_fused_decode_tail_compiles_for_v5e(one_chip, page):
    fn = jax.jit(lambda *a: fused_decode_attention(*a, interpret=False))
    args = _decode_args(page, one_chip) + _tail_args(one_chip)
    _assert_kernel_compiled(fn.lower(*args).compile())


@pytest.mark.parametrize("page", [16, 128])
def test_paged_flash_prefill_compiles_for_v5e(one_chip, page):
    pps = MAX_LEN // page
    pool = _spec((B * pps + 1, KH, page, D), one_chip)
    fn = jax.jit(lambda *a: paged_flash_prefill(*a, interpret=False))
    compiled = fn.lower(_spec((1, CHUNK, H, D), one_chip), pool, pool,
                        _spec((1, pps), one_chip, jnp.int32),
                        _spec((), one_chip, jnp.int32),
                        _spec((), one_chip, jnp.int32)).compile()
    _assert_kernel_compiled(compiled)


@pytest.mark.parametrize("fused", [False, True], ids=["decode", "tail"])
def test_sharded_decode_compiles_on_v5e_1x4(mesh4, fused):
    """kv heads split 20 -> 5 per chip; the kernel runs per shard."""
    rep = NamedSharding(mesh4, P())
    heads = NamedSharding(mesh4, P(None, "model", None, None))
    args = _decode_args(16, rep, pool_sharding=heads)
    if fused:
        args += _tail_args(rep, tail_sharding=heads)
        fn = jax.jit(lambda *a: fused_decode_attention_sharded(
            *a, mesh=mesh4, interpret=False))
    else:
        fn = jax.jit(lambda *a: paged_attention_sharded(
            *a, mesh=mesh4, interpret=False))
    compiled = fn.lower(*args).compile()
    _assert_kernel_compiled(compiled)
    # each chip holds a quarter of the pool, not all of it
    pool_bytes = 2 * np.prod(args[1].shape) * 2
    assert compiled.memory_analysis().argument_size_in_bytes < pool_bytes / 2
