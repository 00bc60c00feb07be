"""Mesh construction.

Every mesh is built by a function (never a module-level constant), so
importing this module never touches jax device state.  The CPU
emulation entry points ask for virtual host devices through
``emulate_host_devices`` before JAX starts a backend.
"""

from __future__ import annotations

import os

import jax


# production mesh geometry, shared with planners that must price the
# production topology without initializing jax devices (dryrun.auto_plan)
PRODUCTION_MULTI_SHAPE = (2, 16, 16)     # (pod, data, model)
PRODUCTION_SINGLE_SHAPE = (16, 16)       # (data, model)


def make_production_mesh(*, multi_pod: bool = False):
    shape = PRODUCTION_MULTI_SHAPE if multi_pod else PRODUCTION_SINGLE_SHAPE
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return jax.make_mesh(shape, axes)


def emulate_host_devices(n: int) -> None:
    """CPU emulation: give XLA's CPU backend ``n`` virtual devices.  A
    no-op unless ``JAX_PLATFORMS`` pins the CPU, so on an accelerator
    the mesh is built from the chips that are there.  Call before JAX
    starts a backend."""
    if os.environ.get("JAX_PLATFORMS", "").split(",")[0].strip() == "cpu":
        jax.config.update("jax_num_cpu_devices", n)


def local_mesh_shape(n: int) -> tuple[int, int, int]:
    """(pod, data, model) over ``n`` devices: two pods and two data
    ranks where ``n`` allows, the rest tensor-parallel — (1,1,1) on one
    chip, (2,2,1) on four, (2,2,2) on eight."""
    pod = 2 if n % 2 == 0 else 1
    data = 2 if (n // pod) % 2 == 0 else 1
    return pod, data, n // (pod * data)


def make_local_mesh(devices=None):
    """(pod, data, model) mesh over ``devices`` (default: every device
    JAX sees), shaped by ``local_mesh_shape``."""
    devices = list(jax.devices() if devices is None else devices)
    return jax.make_mesh(local_mesh_shape(len(devices)),
                         ("pod", "data", "model"), devices=devices)


def mesh_axis_sizes(mesh) -> dict[str, int]:
    return dict(zip(mesh.axis_names, mesh.devices.shape))


def runtime_for_mesh(mesh, *, fsdp: bool = False, sp: bool = False,
                     use_pallas: bool = False, remat: bool = True,
                     remat_policy: str = "none",
                     moe_capacity_factor: float = 1.25,
                     moe_a2a_mode: str = "flat",
                     moe_cluster_weights=None):
    """Build the Runtime matching a production/test mesh."""
    from repro.parallel.sharding import Runtime

    sizes = mesh_axis_sizes(mesh)
    return Runtime(
        tp_axis="model" if "model" in sizes else None,
        dp_axis="data" if "data" in sizes else None,
        pod_axis="pod" if "pod" in sizes else None,
        fsdp_axis="data" if (fsdp and "data" in sizes) else None,
        tp_size=sizes.get("model", 1),
        sp=sp, remat=remat, remat_policy=remat_policy,
        use_pallas=use_pallas,
        moe_capacity_factor=moe_capacity_factor,
        # the ep a2a group is the model axis (experts never shard over
        # pods), so its cluster axis stays None on every shipped mesh
        moe_a2a_mode=moe_a2a_mode,
        moe_cluster_weights=(tuple(moe_cluster_weights)
                             if moe_cluster_weights else None))
