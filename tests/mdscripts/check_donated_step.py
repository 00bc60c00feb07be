"""Donated sharded train steps on the (pod=2, data=2, model=2) mesh of 8
virtual devices (fast tier).

Regression rows:
  * the sharded step donates params and optimizer state, and each
    donated input aliases the output leaf with its own sharding (it was
    once paired with a same-shaped replicated output and failed at run
    time); the initial state is created on its shards, not on device 0;
  * two donated steps of the dense smoke model match the single-device
    losses, and the reported grad_norm (the norm of the mean gradient,
    read by the guard and the NaN watchdog) matches the single-device
    value, for hier with and without int8 on the pod hop.
"""

import os

os.environ.setdefault("XLA_FLAGS", "--xla_force_host_platform_device_count=8")

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

from repro.configs import get_config  # noqa: E402
from repro.launch.mesh import make_local_mesh, runtime_for_mesh  # noqa: E402
from repro.launch.train import data_config, init_training  # noqa: E402
from repro.data import synth_batch  # noqa: E402
from repro.models import Model  # noqa: E402
from repro.parallel.sharding import Runtime  # noqa: E402
from repro.train import TrainConfig  # noqa: E402
from repro.train.optimizer import OptConfig  # noqa: E402

CFG = get_config("qwen2.5-3b", smoke=True)
OPT = OptConfig(lr=5e-3, warmup_steps=1)
DC = data_config(CFG, global_batch=8, seq=32, seed=3)
BATCHES = [{k: jnp.asarray(v) for k, v in synth_batch(DC, i).items()}
           for i in range(2)]


def run(model, tcfg, mesh):
    step, _, _, params, opt = init_training(model, tcfg, mesh)
    if mesh is not None:
        leaf = jax.tree.leaves(params)[0]
        assert len(leaf.sharding.device_set) == mesh.size, leaf.sharding
    losses, norms = [], []
    for b in BATCHES:
        old = jax.tree.leaves(params)[0]
        params, opt, m = step(params, opt, b)
        assert old.is_deleted(), "params were not donated"
        losses.append(float(m["loss"]))
        norms.append(float(m["grad_norm"]))
    return np.array(losses), np.array(norms)


ref_l, ref_n = run(Model(CFG, Runtime()),
                   TrainConfig(comm_mode="flat", opt=OPT), None)
print(f"single-device losses {ref_l} grad_norm {ref_n}")

mesh = make_local_mesh()
assert dict(mesh.shape) == {"pod": 2, "data": 2, "model": 2}, mesh.shape
model = Model(CFG, runtime_for_mesh(mesh))
# int8 on the pod hop is lossy: its bound is looser than the lossless one
for comp, tol in ((None, 5e-3), ("int8", 2e-2)):
    got_l, got_n = run(model, TrainConfig(comm_mode="hier",
                                          dcn_compression=comp, opt=OPT),
                       mesh)
    l_err = float(np.max(np.abs(got_l - ref_l)))
    n_err = float(np.max(np.abs(got_n / ref_n - 1.0)))
    print(f"hier+{comp}: losses {got_l} grad_norm {got_n} "
          f"(max loss diff {l_err:.2e}, max grad_norm rel diff {n_err:.2e})")
    assert l_err < tol, (comp, got_l, ref_l)
    assert n_err < 2e-2, (comp, got_n, ref_n)
    print(f"OK donated hier+{comp} matches single device")

print("ALL-OK")
