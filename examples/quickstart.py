"""Quickstart: the HetCCL hierarchical collectives as a library.

Runs on 8 virtual CPU devices arranged as 2 pods x (2 data x 2 model),
and shows the paper's core move — the same all-reduce, scheduled flat
vs hierarchically — plus the cost model predicting why it matters at
real pod sizes.

    PYTHONPATH=src python examples/quickstart.py
"""

import os

os.environ["JAX_PLATFORMS"] = "cpu"  # virtual CPU devices, never the chip
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P

from repro.core import CommConfig, hier_psum, tpu_multipod
from repro.core import cost_model
from repro.parallel.sharding import shard_map

mesh = jax.make_mesh((2, 2, 2), ("pod", "data", "model"))
grads = jnp.asarray(np.random.default_rng(0).normal(size=(8, 1 << 16)),
                    jnp.float32)


def sync(mode, **kw):
    cfg = CommConfig(mode=mode, pod_axis="pod", intra_axis="data", **kw)
    fn = jax.jit(shard_map(lambda g: hier_psum(g, cfg), mesh=mesh,
                               in_specs=P(("pod", "data")), out_specs=P(None),
                               check_vma=False))
    return fn(grads)


flat = sync("flat")
hier = sync("hier")
pipe = sync("hier_pipelined", n_chunks=4)
comp = sync("hier", compression="int8")

print("flat == hier:", bool(jnp.allclose(flat, hier, atol=1e-4)))
print("flat == hier_pipelined:", bool(jnp.allclose(flat, pipe, atol=1e-4)))
rel = float(jnp.mean(jnp.abs(flat - comp) / (jnp.abs(flat) + 1e-3)))
print(f"int8-compressed DCN hop mean rel err: {rel:.4f}")

# why it matters at scale: the cost model on 2 x 256-chip v5e pods
topo = tpu_multipod(2, 256)
n = 256 << 20  # 256 MiB of gradients per chip
est = cost_model.estimate_hier_collective(topo, "all_reduce", n, n_chunks=8)
host = cost_model.flat_host_forwarding_time(topo, "all_reduce", n)
print(f"\n2x256-chip all-reduce of {n >> 20} MiB/chip:")
print(f"  hierarchical (pipelined): {est.pipelined_s * 1e3:8.1f} ms")
print(f"  hierarchical (sequential):{est.sequential_s * 1e3:8.1f} ms")
print(f"  host-forwarding baseline: {host * 1e3:8.1f} ms")
print(f"  speedup vs host-forwarding: {host / est.pipelined_s:.1f}x")
