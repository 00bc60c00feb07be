"""Benchmark harness: one function per paper table/figure.

Prints ``name,us_per_call,derived`` CSV.  Also includes real-JAX
microbenchmarks of the framework's own hot paths (collective wire-byte
verification via HLO, kernel wall-times in interpret mode).
"""

from __future__ import annotations

import os
import pathlib
import sys
import time

# CPU emulation harness (interpret-mode kernels, cost model): pinned to
# the CPU for itself and the children it starts
os.environ["JAX_PLATFORMS"] = "cpu"

# make `python benchmarks/run.py` work from anywhere: the benchmarks
# package lives next to this file's parent
sys.path.insert(0, str(pathlib.Path(__file__).resolve().parent.parent))


def _kernel_microbench():
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.kernels import ops, ref

    rows = []
    rng = np.random.default_rng(0)
    q = jnp.asarray(rng.normal(size=(1, 512, 4, 128)), jnp.float32)
    k = jnp.asarray(rng.normal(size=(1, 512, 2, 128)), jnp.float32)
    v = jnp.asarray(rng.normal(size=(1, 512, 2, 128)), jnp.float32)
    for name, fn in (
        ("kernel_flash_attn_interp",
         jax.jit(lambda a, b, c: ops.flash_attention(a, b, c, interpret=True))),
        ("kernel_attn_reference",
         jax.jit(lambda a, b, c: ref.attention(a, b, c))),
    ):
        fn(q, k, v).block_until_ready()
        t0 = time.perf_counter()
        for _ in range(3):
            fn(q, k, v).block_until_ready()
        rows.append((name, (time.perf_counter() - t0) / 3 * 1e6, "cpu-interp"))
    x = jnp.asarray(rng.normal(size=(1 << 18,)), jnp.float32)
    qfn = jax.jit(lambda a: ops.quant_int8(a, interpret=True)[0])
    qfn(x).block_until_ready()
    t0 = time.perf_counter()
    for _ in range(3):
        qfn(x).block_until_ready()
    rows.append(("kernel_quant_int8_4M", (time.perf_counter() - t0) / 3 * 1e6,
                 "4x_wire_compression"))
    return rows


def main() -> None:
    from benchmarks import paper_figures

    want = set(sys.argv[1:])  # e.g. `run.py fig11 fig9`; empty = everything
    print("name,us_per_call,derived")
    for key, fig_fn in paper_figures.ALL_FIGURES:
        if want and key not in want:
            continue
        try:
            for name, us, derived in fig_fn():
                print(f"{name},{us:.1f},{derived}")
        except Exception as e:  # noqa: BLE001
            print(f"{fig_fn.__name__},0,ERROR:{type(e).__name__}:{e}")
    if not want:
        for name, us, derived in _kernel_microbench():
            print(f"{name},{us:.1f},{derived}")
    sys.stdout.flush()


if __name__ == "__main__":
    main()
