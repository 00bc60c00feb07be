"""Compile-only checks for the TPU: the int8 codec kernels at a real
gradient width (one 2048x11008 qwen2.5-3b MLP weight) and flash
attention at qwen2.5-3b widths, compiled for one chip of a described
v5e:2x2 slice, and the packed gradient's reduce-scatter over two of its
chips.  Nothing runs; a pass says the TPU compiler accepts the kernels
(tiling, VMEM) and emits them as Mosaic custom calls, and keeps a
whole-span reduce-scatter a plain one.

The topology is described inside a module fixture, never at import:
only one process may load the TPU library, and test workers each import
this file.  The compilation cache is off around these compiles, since a
compile for a described chip cannot be read back without one."""

import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, NamedSharding, SingleDeviceSharding
from jax.sharding import PartitionSpec as P

from repro.core import packing, primitives
from repro.kernels import ops
from repro.kernels import quant as qk
from repro.parallel.sharding import shard_map

N = 2048 * 11008          # one real-width gradient buffer
NB = N // qk.BLOCK


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    try:
        yield topologies.get_topology_desc(platform="tpu",
                                           topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 - no TPU library here
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    finally:
        jax.config.update("jax_enable_compilation_cache", was)
        compilation_cache.reset_cache()


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _codec_case(name, sharding):
    def arg(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)

    flat, scale = arg((N,), jnp.float32), arg((NB,), jnp.float32)
    return {
        "amax_block": (lambda x: qk.amax_block_call(x, interpret=False),
                       [flat]),
        "quant_scaled": (lambda x, s: qk.quant_scaled_call(
            x, s, interpret=False), [flat, scale]),
        "dequant_int8": (lambda q, s: qk.dequant_int8_call(
            q, s, interpret=False), [arg((NB, qk.BLOCK), jnp.int8), scale]),
        "dequant_int32": (lambda q, s: qk.dequant_int8_call(
            q, s, interpret=False), [arg((NB, qk.BLOCK), jnp.int32), scale]),
        "quant_int8": (lambda x: qk.quant_int8_call(x, interpret=False),
                       [flat]),
    }[name]


@pytest.mark.parametrize("name", ["amax_block", "quant_scaled",
                                  "dequant_int8", "dequant_int32",
                                  "quant_int8"])
def test_codec_kernel_compiles_for_v5e(name, one_chip):
    fn, args = _codec_case(name, one_chip)
    compiled = jax.jit(fn).lower(*args).compile()
    assert compiled.as_text().count("tpu_custom_call") == 1


def test_flash_attention_forward_compiles_for_v5e(one_chip):
    # qwen2.5-3b: 16 query heads, 2 KV heads, head dim 128; seq 4096
    q = jax.ShapeDtypeStruct((1, 4096, 16, 128), jnp.bfloat16,
                             sharding=one_chip)
    kv = jax.ShapeDtypeStruct((1, 4096, 2, 128), jnp.bfloat16,
                              sharding=one_chip)
    compiled = jax.jit(lambda q, k, v: ops.flash_attention(
        q, k, v, causal=True, interpret=False)).lower(q, kv, kv).compile()
    assert compiled.as_text().count("tpu_custom_call") >= 1


@pytest.fixture(scope="module")
def pod_data(topo):
    return Mesh(np.array(topo.devices).reshape(2, 2), ("pod", "data"))


@pytest.mark.parametrize("whole_spans", [True, False])
def test_packed_reduce_scatter_on_v5e(pod_data, whole_spans):
    """OLMo-1B's packed float32 gradient (the four-chip cell's), as the
    layout pads it, reduce-scatters over the two chips of ``data`` as
    one plain ``reduce-scatter``; unpadded, the compiler pads it in an
    ``all-reduce-scatter`` fusion and mends the shard with a permute."""
    used = 639_893_504
    n = packing.padded_size(used, 4, world=4) if whole_spans else used
    f = shard_map(lambda v: primitives.hom_reduce_scatter(v, "data"),
                  mesh=pod_data, in_specs=P(), out_specs=P("data"),
                  check_vma=False)
    x = jax.ShapeDtypeStruct((n,), jnp.float32,
                             sharding=NamedSharding(pod_data, P()))
    text = jax.jit(f).lower(x).compile().as_text()
    assert (" reduce-scatter(" in text) == whole_spans
    assert ("calls=%all-reduce-scatter" in text) != whole_spans
    assert ("collective-permute" in text) != whole_spans
