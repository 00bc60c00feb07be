#!/usr/bin/env python3
"""Smoke run of the HetCCL training step on a TPU.

    python3 chip_smoke.py               # one chip
    python3 chip_smoke.py --four-chip   # pod=2 x data=2 over four chips

Drives the library path a user takes (``get_config`` -> ``Model`` ->
``make_train_step`` -> ``DataConfig``/``Prefetcher``, shared with
``repro.launch.train``) on qwen2.5-3b at its published widths, with
only the depth cut, random weights from a seed and one 4096-token
sequence per chip.

* One chip: a (pod, data, model) = (1, 1, 1) mesh running the ``hier``
  schedule with the packed sync and int8 on the pod hop, so the
  shard_map step, the schedule-IR executor, the packed data path and
  the compiled int8 codec kernels all run.  A few steps on one repeated
  batch must give finite losses that start near ln(vocab) and fall.
  Then the codec runs on one real-width gradient buffer and on one
  whose last kernel grid step is ragged, and must be bit-identical to
  its jnp mirror.
* ``--four-chip``: the same model on a (2, 2, 1) mesh, one sequence per
  chip.  ``flat`` (one uncompressed all-reduce) is the reference;
  ``hier`` and ``hier_pipelined`` + int8 must follow its per-step
  losses within the bounds below, and the hierarchical gradient sync
  alone must equal ``lax.psum`` over (pod, data) exactly.

Step times are printed as smoke timings: one short run, not a
benchmark.  Without a TPU the script exits non-zero and prints no
result; it never falls back to the CPU.  The last line of a passing run
is one JSON object naming the device.
"""

from __future__ import annotations

import argparse
import contextlib
import dataclasses
import json
import math
import os
import pathlib
import sys
import time

sys.path.insert(0, str(pathlib.Path(__file__).resolve().parent / "src"))

ARCH = "qwen2.5-3b"
# Of the 36 published layers, the most that leave room on one 16 GB v5e:
# the compiled one-chip step (bf16 weights and grads, f32 Adam moments,
# the packed int8 sync and the f32 logits of 4096 tokens) needs
# 13.3 GiB at 6 layers; 7 layers pass the compiler with no headroom and
# 8 exceed the chip's 15.75 GiB.
LAYERS = 6
SEQ = 4096          # one train_4k sequence per chip (256 over 256 chips)
STEPS = 5
# Adam with no warmup moves every weight by about LR on each early step,
# so the loss on one repeated batch swings before it falls.  A small LR
# keeps the swing, and with it the amplification of the rounding
# differences that the four-chip comparison bounds, small.
LR = 1e-4
# one real-width gradient (an MLP weight: 22016 blocks, 86 whole grid
# steps of the codec kernels), then 257 blocks, whose last grid step is
# ragged (one row of 256) as a packed gradient buffer's usually is
CODEC_SHAPES = ((2048, 11008), (257, 1024))
# |loss - flat loss| bounds of the four-chip phase.  Step 0 is the same
# forward pass in every mode.  After it, hier and flat add the same bf16
# gradients in a different order (reduce-scatter, pod hop, all-gather
# versus one all-reduce), so sums may differ in their last bf16 bit and
# Adam's normalised update turns such a difference into at most one
# lr-sized step on the few weights whose gradient is near zero.  int8
# on the pod hop adds a quantisation error of up to half a scale step
# per element, which is why its bound is looser.
HIER_TOL = 2e-2
INT8_TOL = 1e-1


def log(msg: str) -> None:
    print(msg, flush=True)


class SmokeFailure(RuntimeError):
    """A phase produced a wrong result."""


def check(ok: bool, what: str) -> None:
    # explicit raise, not assert: the checks must hold under python -O
    if not ok:
        raise SmokeFailure(what)


def smoke_config(layers: int = LAYERS):
    from repro.configs import get_config

    return dataclasses.replace(get_config(ARCH), n_layers=layers)


def describe_config(cfg) -> str:
    from repro.configs import get_config

    published = get_config(ARCH).n_layers
    return (f"config {cfg.name}: d_model {cfg.d_model}, {cfg.n_heads}x"
            f"{cfg.head_dim} query heads, {cfg.n_kv_heads} KV heads, d_ff "
            f"{cfg.d_ff}, vocab {cfg.vocab_size}"
            f"{' tied' if cfg.tie_embeddings else ''}"
            f"{', QKV bias' if cfg.qkv_bias else ''}; depth cut to "
            f"{cfg.n_layers} of {published} layers "
            f"({cfg.param_count() / 1e6:.0f}M params)")


def peak_bytes() -> int | None:
    import jax

    stats = jax.devices()[0].memory_stats() or {}
    return stats.get("peak_bytes_in_use")


def train_run(cfg, mesh, mode: str, compression: str | None,
              seq: int = SEQ, steps: int = STEPS, seed: int = 0) -> dict:
    """Build the step through the library path, compile it, and take
    ``steps`` steps on one repeated batch.  Returns plain numbers only,
    so the run's device state is freed when it returns."""
    import jax
    import jax.numpy as jnp

    from repro.data import Prefetcher
    from repro.launch import train as train_lib
    from repro.launch.mesh import runtime_for_mesh
    from repro.models import Model
    from repro.train import TrainConfig
    from repro.train.optimizer import OptConfig

    model = Model(cfg, runtime_for_mesh(mesh))
    tcfg = TrainConfig(comm_mode=mode, dcn_compression=compression,
                       packed=True, opt=OptConfig(lr=LR, warmup_steps=1))
    step_fn, _, _, params, opt = train_lib.init_training(model, tcfg, mesh,
                                                         seed)
    n_dp = mesh.shape["pod"] * mesh.shape["data"]
    pre = Prefetcher(train_lib.data_config(cfg, n_dp, seq, seed))
    try:
        _, batch = pre.get(timeout=60.0)
    finally:
        pre.close()
    batch = {k: jnp.asarray(v) for k, v in batch.items()}

    t0 = time.perf_counter()
    compiled = step_fn.lower(params, opt, batch).compile()
    compile_s = time.perf_counter() - t0
    n_kernels = compiled.as_text().count("tpu_custom_call")
    ma = compiled.memory_analysis()
    label = mode + (f"+{compression}" if compression else "")
    log(f"[{label}] compiled in {compile_s:.1f} s; tpu_custom_call in the "
        f"step's HLO: {n_kernels}")
    if ma is not None:
        log(f"[{label}] compiled step memory (XLA memory_analysis): "
            f"arguments {ma.argument_size_in_bytes} B, temp "
            f"{ma.temp_size_in_bytes} B, outputs {ma.output_size_in_bytes} "
            f"B of which {ma.alias_size_in_bytes} B alias donated arguments")
    losses = []
    for i in range(steps):
        t = time.perf_counter()
        params, opt, m = compiled(params, opt, batch)
        loss = float(m["loss"])
        dt = time.perf_counter() - t
        losses.append(loss)
        log(f"[{label}] step {i} loss {loss:.6f} grad_norm "
            f"{float(m['grad_norm']):.6f} "
            f"(smoke timing, not a benchmark: {dt * 1e3:.1f} ms)")
    del params, opt
    return {"losses": losses, "compile_s": compile_s,
            "tpu_custom_call": n_kernels}


def check_training(res: dict, vocab: int) -> None:
    losses = res["losses"]
    check(all(math.isfinite(x) for x in losses), f"non-finite loss: {losses}")
    check(abs(losses[0] - math.log(vocab)) < 0.5,
          f"step 0 loss {losses[0]} is not within 0.5 of ln({vocab}) = "
          f"{math.log(vocab)}")
    check(losses[-1] < losses[0], f"loss did not fall: {losses}")


@contextlib.contextmanager
def _codec_backend(pallas: bool):
    # compression.py reads REPRO_PALLAS_QUANT at trace time
    old = os.environ.get("REPRO_PALLAS_QUANT")
    os.environ["REPRO_PALLAS_QUANT"] = "1" if pallas else "0"
    try:
        yield
    finally:
        if old is None:
            del os.environ["REPRO_PALLAS_QUANT"]
        else:
            os.environ["REPRO_PALLAS_QUANT"] = old


def codec_run(shape, seed: int = 0) -> dict:
    """The int8 codec of ``core/compression.py`` on one gradient-sized
    buffer, through the Pallas kernels and through the jnp mirror:
    shared-scale encode (amax + scaled quantise), fused quantise, and
    the decode of int8 and of int32 ring sums.  Every output must be
    bit-identical between the two."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.core import compression

    x = jax.random.normal(jax.random.key(seed), shape, jnp.float32)
    x = x * jnp.exp(jax.random.normal(jax.random.key(seed + 1),
                                      (shape[0], 1), jnp.float32))

    def codec(v):
        q, s = compression.int8_encode(v, None)
        fq, fs = compression.quantize_int8(v)
        dec8 = compression.dequantize_int8(q, s, v.size)
        # the ring hands the decode int32 sums (here: three equal ranks)
        dec32 = compression.dequantize_int8(q.astype(jnp.int32) * 3, s,
                                            v.size)
        return {"q": q, "scale": s, "fused_q": fq, "fused_scale": fs,
                "decode_int8": dec8, "decode_int32": dec32}

    out = {}
    for pallas in (True, False):
        with _codec_backend(pallas):
            fn = jax.jit(lambda v: codec(v))
            compiled = fn.lower(x).compile()
            out[pallas] = (jax.device_get(compiled(x)),
                           compiled.as_text().count("tpu_custom_call"))
    (kern, n_kernels), (mirror, _) = out[True], out[False]
    same = {k: bool(np.array_equal(np.asarray(kern[k]).view(np.uint8),
                                   np.asarray(mirror[k]).view(np.uint8)))
            for k in kern}
    log(f"[codec] {shape[0]}x{shape[1]} f32 buffer "
        f"({x.size // compression.BLOCK} blocks of {compression.BLOCK}): "
        f"bit-identical to the jnp mirror: {same}; tpu_custom_call "
        f"{n_kernels}")
    check(all(same.values()), f"codec differs from the jnp mirror: {same}")
    return {"tpu_custom_call": n_kernels}


def sync_check_fn(cfg, mesh):
    """The jitted sync-only check of ``sync_run`` and the gradient tree
    shapes it builds: key -> True when ``tree_hier_psum`` (packed
    ``hier``) equals ``lax.psum`` over (pod, data) on every leaf."""
    import jax
    import jax.numpy as jnp
    from jax import lax
    from jax.sharding import PartitionSpec as P

    from repro.core import collectives as coll
    from repro.launch.mesh import runtime_for_mesh
    from repro.models import Model
    from repro.parallel.sharding import shard_map
    from repro.train import TrainConfig

    model = Model(cfg, runtime_for_mesh(mesh))
    pshape = jax.eval_shape(model.init, jax.random.key(0))
    ccfg = TrainConfig(comm_mode="hier").comm_config(model.rt)
    axes = ("pod", "data")

    def body(key):
        rank = lax.axis_index("pod") * lax.psum(1, "data") + lax.axis_index(
            "data")
        leaves, treedef = jax.tree.flatten(pshape)
        keys = jax.random.split(jax.random.fold_in(key, rank), len(leaves))
        grads = jax.tree.unflatten(treedef, [
            jax.random.randint(k, s.shape, -8, 9).astype(s.dtype)
            for k, s in zip(keys, leaves)])
        hier = coll.tree_hier_psum(grads, ccfg, packed=True)
        ref = jax.tree.map(lambda g: lax.psum(g, axes), grads)
        same = [jnp.array_equal(a, b) for a, b in
                zip(jax.tree.leaves(hier), jax.tree.leaves(ref))]
        return jnp.all(jnp.stack(same))

    fn = jax.jit(shard_map(body, mesh=mesh, in_specs=P(), out_specs=P(),
                           check_vma=False))
    return fn, pshape


def sync_run(cfg, mesh, seed: int = 0) -> None:
    """The hierarchical gradient sync alone must equal ``lax.psum`` over
    (pod, data) exactly.  Each device holds its own small integers, so
    every order of summation gives the same bits."""
    import jax

    fn, pshape = sync_check_fn(cfg, mesh)
    ok = bool(fn(jax.random.key(seed)))
    leaves = jax.tree.leaves(pshape)
    n = sum(math.prod(s.shape) for s in leaves)
    log(f"[sync] tree_hier_psum(hier, packed) == lax.psum over "
        f"('pod', 'data') on {len(leaves)} gradient leaves ({n} elements): "
        f"{ok}")
    check(ok, "tree_hier_psum differs from lax.psum")


def one_chip(cfg, seq: int = SEQ, steps: int = STEPS,
             codec_shapes=CODEC_SHAPES) -> dict:
    """The default phase: hier + packed + int8 on a (1, 1, 1) mesh, then
    the codec on one real-width buffer."""
    import jax

    from repro.launch.mesh import make_local_mesh

    mesh = make_local_mesh(jax.devices()[:1])
    log(describe_config(cfg))
    log(f"mesh {dict(mesh.shape)}; comm_mode hier, packed sync, "
        f"dcn_compression int8; {seq} tokens per step")
    res = train_run(cfg, mesh, "hier", "int8", seq, steps)
    check_training(res, cfg.vocab_size)
    log(f"[train] peak_bytes_in_use {peak_bytes()}")
    codec = [codec_run(shape) for shape in codec_shapes]
    log(f"[codec] peak_bytes_in_use {peak_bytes()}")
    return {"train": res, "codec": codec}


def four_chip(cfg, seq: int = SEQ, steps: int = STEPS) -> dict:
    """flat (reference), hier and hier_pipelined+int8 on a (2, 2, 1)
    mesh, then the sync-only equality check."""
    import jax

    from repro.launch.mesh import make_local_mesh

    mesh = make_local_mesh(jax.devices()[:4])
    check(dict(mesh.shape) == {"pod": 2, "data": 2, "model": 1},
          f"unexpected four-chip mesh {dict(mesh.shape)}")
    log(describe_config(cfg))
    log(f"mesh {dict(mesh.shape)}; one {seq}-token sequence per chip")
    runs = {}
    for name, mode, comp in (("flat", "flat", None), ("hier", "hier", None),
                             ("hier_pipelined+int8", "hier_pipelined",
                              "int8")):
        runs[name] = train_run(cfg, mesh, mode, comp, seq, steps)
        check_training(runs[name], cfg.vocab_size)
        log(f"[{name}] peak_bytes_in_use {peak_bytes()}")
    ref = runs["flat"]["losses"]
    for name, tol in (("hier", HIER_TOL), ("hier_pipelined+int8", INT8_TOL)):
        err = max(abs(a - b) for a, b in zip(runs[name]["losses"], ref))
        log(f"[compare] {name} vs flat: max |loss diff| {err:.6g} "
            f"(bound {tol})")
        check(err <= tol, f"{name} losses {runs[name]['losses']} leave "
                          f"flat's {ref} by {err} > {tol}")
    sync_run(cfg, mesh)
    log(f"[sync] peak_bytes_in_use {peak_bytes()}")
    return runs


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--four-chip", action="store_true",
                    help="run only the four-chip comparison")
    args = ap.parse_args(argv)

    import jax

    devices = jax.devices()
    if devices[0].platform != "tpu":
        print(f"chip_smoke: no TPU found (JAX platform "
              f"{devices[0].platform!r}); nothing was run",
              file=sys.stderr)
        return 1
    if args.four_chip and len(devices) < 4:
        print(f"chip_smoke: --four-chip needs 4 TPU devices, found "
              f"{len(devices)}", file=sys.stderr)
        return 1

    from repro.launch.train import enable_compile_cache

    enable_compile_cache()
    log(f"device {devices[0].device_kind} x{len(devices)}; jax "
        f"{jax.__version__}")
    cfg = smoke_config()
    if args.four_chip:
        runs = four_chip(cfg)
        for name, res in runs.items():
            if "int8" in name:
                check(res["tpu_custom_call"] > 0,
                      f"{name}: no Mosaic kernel in the compiled step")
    else:
        res = one_chip(cfg)
        check(res["train"]["tpu_custom_call"] > 0,
              "hier+int8: no Mosaic kernel in the compiled step")
        check(all(c["tpu_custom_call"] > 0 for c in res["codec"]),
              "codec: no Mosaic kernel in the compiled codec")
    print(json.dumps({"ok": True, "device": {
        "platform": devices[0].platform, "kind": devices[0].device_kind,
        "count": len(devices)}}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
