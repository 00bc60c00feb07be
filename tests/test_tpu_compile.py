"""Compile-only checks for the TPU: the int8 codec kernels at a real
gradient width (one 2048x11008 qwen2.5-3b MLP weight) and flash
attention at qwen2.5-3b widths, compiled for one chip of a described
v5e:2x2 slice.  Nothing runs; a pass says the TPU compiler accepts the
kernels (tiling, VMEM) and emits them as Mosaic custom calls.

The topology is described inside a module fixture, never at import:
only one process may load the TPU library, and test workers each import
this file.  The compilation cache is off around these compiles, since a
compile for a described chip cannot be read back without one."""

import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels import ops
from repro.kernels import quant as qk

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
