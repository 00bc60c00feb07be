"""Elastic checkpoint/restart across device counts (8 virtual devices).

Simulates the pod-failure recovery path: train sharded on the full
(2,2,2) mesh, checkpoint, then resume the SAME global state
single-device (cluster shrank), step, checkpoint again, and resume back
on the mesh (cluster recovered).  Loss trajectories must line up with
an uninterrupted single-device run on the same deterministic data
stream, proving restart-safety and topology independence.
"""

import os
import tempfile

os.environ.setdefault("XLA_FLAGS", "--xla_force_host_platform_device_count=8")

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

from repro.configs import get_config  # noqa: E402
from repro.data import DataConfig, synth_batch  # noqa: E402
from repro.models import Model  # noqa: E402
from repro.parallel.sharding import Runtime  # noqa: E402
from repro.runtime import CheckpointManager  # noqa: E402
from repro.train import TrainConfig, make_train_step  # noqa: E402
from repro.train.optimizer import OptConfig  # noqa: E402

cfg = get_config("qwen2.5-3b", smoke=True)
OPT = OptConfig(lr=5e-3, warmup_steps=1)
DC = DataConfig(vocab_size=cfg.vocab_size, global_batch=4, seq_len=32, seed=9)


def to_batch(step):
    return {k: jnp.asarray(v) for k, v in synth_batch(DC, step).items()}


mesh = jax.make_mesh((2, 2, 2), ("pod", "data", "model"))
rt_mesh = Runtime(tp_axis="model", dp_axis="data", pod_axis="pod", tp_size=2)
rt_one = Runtime()

model_m = Model(cfg, rt_mesh)
model_1 = Model(cfg, rt_one)

build, init = make_train_step(model_m, TrainConfig(comm_mode="hier", opt=OPT),
                              mesh=mesh, donate=False)
params, opt = init(jax.random.key(0))
pshape = jax.tree.map(lambda p: jax.ShapeDtypeStruct(p.shape, p.dtype), params)
step_m, _ = build(pshape)
step_1, _ = make_train_step(model_1, TrainConfig(comm_mode="flat", opt=OPT),
                            mesh=None)

# --- uninterrupted single-device reference ---------------------------------
# the same initial state as plain host values (init places it on its
# mesh shards, whose sharded types the single-device step cannot take)
p_ref, o_ref = jax.tree.map(lambda x: jnp.asarray(np.asarray(x)),
                            init(jax.random.key(0)))
ref_losses = []
for i in range(6):
    p_ref, o_ref, m = step_1(p_ref, o_ref, to_batch(i))
    ref_losses.append(float(m["loss"]))

# --- phase 1: 2 steps on the full mesh --------------------------------------
tmp = tempfile.mkdtemp()
ckpt = CheckpointManager(tmp)
losses = []
for i in range(2):
    params, opt, m = step_m(params, opt, to_batch(i))
    losses.append(float(m["loss"]))
ckpt.save(2, (params, opt))

# --- phase 2: "cluster shrank" -> resume on 1 device -------------------------
_, (p1, o1), _ = ckpt.restore((params, opt))
p1 = jax.device_put(p1, jax.devices()[0])
o1 = jax.device_put(o1, jax.devices()[0])
for i in range(2, 4):
    p1, o1, m = step_1(p1, o1, to_batch(i))
    losses.append(float(m["loss"]))
ckpt.save(4, (p1, o1))

# --- phase 3: "cluster recovered" -> resume on the mesh ----------------------
_, (p2, o2), _ = ckpt.restore((p1, o1))
for i in range(4, 6):
    p2, o2, m = step_m(p2, o2, to_batch(i))
    losses.append(float(m["loss"]))

err = max(abs(a - b) for a, b in zip(losses, ref_losses))
print("elastic losses:", ["%.4f" % l for l in losses])
print("reference     :", ["%.4f" % l for l in ref_losses])
assert err < 0.05, (losses, ref_losses, err)
print(f"OK elastic mesh->single->mesh restart matches uninterrupted run "
      f"(maxerr {err:.4f})")
print("ALL-OK")
