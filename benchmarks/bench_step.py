"""Packed-vs-per-leaf gradient data-path benchmark (BENCH_step.json).

Measures the emulated 8-device gradient-sync step time and effective
GB/s per comm mode for three data paths:

  * ``per_leaf`` — one hierarchical collective per gradient leaf (the
    per-message staging HetCCL §4.1 eliminates; what naive DDP and the
    fsdp per-leaf sync do);
  * ``legacy``   — the pre-packing dtype-bucketed path: per-step
    re-flatten + per-chunk/per-codec re-pads
    (``tree_hier_psum(packed=False)``);
  * ``packed``   — the zero-copy packed data path (``core/packing.py``,
    DESIGN.md §11): persistent layout, one pack, slice-only unpack, no
    re-pads.

The measured step is the gradient sync plus an SGD-style param update
(the data-path hot loop of every comm mode we ship), NOT a model
forward/backward — this benchmark isolates the comm data path the PR
optimizes; EXPERIMENTS.md records the numbers.  Times are medians over
``--steps`` jitted executions on 8 virtual CPU devices, so they are an
*emulation* trajectory (relative deltas meaningful, absolute times
not).

Each cell also records the **planner's data-path decision** for this
payload (``core.planner.plan(packed=True, n_leaves=...)``), priced on
a topology whose α–β constants are *probed from the emulated fabric
in-run* — the planner must predict the fabric the measurement runs
on, or the decision is not testable.  ``planner_data_path`` is
"packed" or "per_leaf", and ``speedup_planner_vs_per_leaf`` is the
measured step ratio of the planner-CHOSEN path over per_leaf — a
per-leaf fallback scores exactly 1.0, so the invariant "the
planner-chosen configuration never loses to per-leaf" is checkable
from the JSON alone (the CI perf-smoke job gates on it).  The
real-fabric decision (``tpu_multipod`` constants, where per-leaf pays
~µs-scale α 450 times and packing wins) is recorded alongside as
``planner_data_path_fabric`` for contrast.

Writes ``BENCH_step.json`` at the repo root.  The acceptance gate of
the packed-data-path PRs: >= 1.25x step-time improvement packed vs
the legacy (per-step re-flatten + re-pad) packed path on the
``hier_pipelined`` int8 cell, and the planner invariant above.  (An
earlier revision gated packed-vs-per-leaf at the measured 1.861x —
that figure was measured against a per-leaf baseline inflated ~1.5x
by the pipeline-fill bug this PR fixes (k+2 pod rounds per leaf);
with the fill fixed, per-leaf on the CPU emulation is α-cheap and
ties packed, which is exactly the regime the planner's per-leaf
fallback now detects.)

Run:  PYTHONPATH=src python benchmarks/bench_step.py [--quick]
"""

import os

# CPU emulation tool: pinned to the CPU so it never claims an accelerator
os.environ["JAX_PLATFORMS"] = "cpu"
os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "")
                           + " --xla_force_host_platform_device_count=8")

import argparse      # noqa: E402
import json          # noqa: E402
import pathlib       # noqa: E402
import statistics    # noqa: E402
import sys           # noqa: E402
import time          # noqa: E402

import jax           # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np   # noqa: E402
from jax.sharding import PartitionSpec as P  # noqa: E402

from repro.core import overlap  # noqa: E402
from repro.core.collectives import CommConfig, hier_psum, tree_hier_psum  # noqa: E402
from repro.parallel.sharding import shard_map  # noqa: E402

ROOT = pathlib.Path(__file__).resolve().parent.parent


def grad_tree(n_layers: int, d: int, vocab: int):
    """A transformer-shaped gradient tree with UNSTACKED layers: every
    layer is its own subtree, so the per_leaf baseline really pays one
    collective per parameter tensor (the per-message staging regime)."""
    rng = np.random.default_rng(0)

    def arr(*shape):
        return jnp.asarray(rng.normal(size=shape), jnp.float32)

    tree = {"embed": arr(vocab, d), "lm_head": arr(vocab, d),
            "final_norm": arr(d)}
    for i in range(n_layers):
        tree[f"layer_{i:02d}"] = {"wq": arr(d, d), "wo": arr(d, d),
                                  "norm": arr(d)}
    return tree


def make_step(mode: str, n_chunks: int, compression, path: str, mesh,
              specs, lr: float = 1e-3):
    """One data-path step: gradient sync + SGD update, jitted over the
    8-device mesh."""
    cfg = CommConfig(mode="hier" if mode == "hier_overlap" else mode,
                     pod_axis="pod", intra_axis="data",
                     n_chunks=n_chunks, compression=compression)

    def sync(grads):
        if mode == "hier_overlap":
            return overlap.tree_hier_psum_overlap(
                grads, cfg, packed=(path == "packed"))
        if path == "per_leaf":
            return jax.tree.map(lambda g: hier_psum(g, cfg), grads)
        return tree_hier_psum(grads, cfg, packed=(path == "packed"))

    def step(params, grads):
        g = sync(grads)
        return jax.tree.map(lambda p, gi: p - lr * gi, params, g)

    return jax.jit(shard_map(step, mesh=mesh, in_specs=(specs, specs),
                             out_specs=specs, check_vma=False))


def _time_min(fn, *xs, reps: int = 5) -> float:
    jax.block_until_ready(fn(*xs))
    best = float("inf")
    for _ in range(reps):
        t0 = time.perf_counter()
        jax.block_until_ready(fn(*xs))
        best = min(best, time.perf_counter() - t0)
    return best


def calibrate_emulated_topology(mesh, _cache: list = []):
    """Probe the α–β constants of the *emulated* fabric and build the
    matching 2-pod x 4-chip topology, so the planner's packed-vs-
    per-leaf decision prices the machine the measurement runs on.

    α is probed in the per-leaf regime — a stream of 64 independent
    tiny collectives in ONE program, because XLA overlaps their
    dispatch and a lone barrier-bound collective would overstate the
    effective per-message latency ~10x.  β comes from one payload-bound
    collective; the pack/staging engine (``d2d_Bps``) from a
    payload-sized elementwise pass (what a pack write costs on the
    shared memory bus).  Returns ``(topology, constants_dict)``."""
    if _cache:
        return _cache[0]
    from repro.core import topology

    n_small = 64
    small = [jnp.full((256,), float(i + 1), jnp.float32)
             for i in range(n_small)]
    f_alpha = jax.jit(shard_map(
        lambda *t: [jax.lax.psum(x, "data") for x in t], mesh=mesh,
        in_specs=(P(),) * n_small, out_specs=[P(None)] * n_small,
        check_vma=False))
    # β from a one-pass collective (reduce-scatter): the model prices
    # RS and AG as separate α–β phases, so fitting β from an all-reduce
    # (two data passes) would double-charge every phase
    big = jnp.ones((2 * 1024 * 1024,), jnp.float32)          # 8 MB
    f_beta = jax.jit(shard_map(
        lambda x: jax.lax.psum_scatter(x, "data", tiled=True), mesh=mesh,
        in_specs=(P(),), out_specs=P("data"), check_vma=False))
    # the pack/unpack engine runs replicated on every device thread at
    # once (each writes the full payload), so probe the CONTENDED pass:
    # all 8 threads streaming the buffer through the shared memory bus
    f_copy = jax.jit(shard_map(lambda x: x * jnp.float32(1.0000001),
                               mesh=mesh, in_specs=(P(),),
                               out_specs=P(), check_vma=False))
    alpha = _time_min(f_alpha, *small) / n_small
    beta_Bps = big.nbytes / max(_time_min(f_beta, big) - alpha, 1e-9)
    d2d_Bps = big.nbytes / max(_time_min(f_copy, big), 1e-9)
    topo = topology.HetTopology(tuple(
        topology.Cluster(f"pod{i}", n_nodes=1, devs_per_node=4,
                         nics_per_node=4, nic_Bps=beta_Bps / 4,
                         intra_Bps=beta_Bps, d2d_Bps=d2d_Bps,
                         alpha_native_s=alpha, alpha_hetccl_s=alpha,
                         alpha_host_s=10 * alpha)
        for i in range(2)))
    consts = {"alpha_us": round(alpha * 1e6, 2),
              "collective_GBps": round(beta_Bps / 1e9, 4),
              "d2d_GBps": round(d2d_Bps / 1e9, 4)}
    _cache.append((topo, consts))
    return _cache[0]


def planner_data_path(topo, total_bytes: int, n_leaves: int, compression,
                      _cache: dict = {}) -> str:
    """The planner's packed-vs-per-leaf decision for this payload on
    ``topo`` (``plan(packed=True, n_leaves=...)`` — the per-leaf
    fallback of core/planner.py)."""
    from repro.core import planner

    key = (id(topo), total_bytes, n_leaves, compression)
    if key not in _cache:
        comps = (None,) if compression is None else (None, compression)
        p = planner.plan(topo, [total_bytes], compressions=comps,
                         flat_mechanism="native", try_balanced=False,
                         packed=True, n_leaves=n_leaves)
        _cache[key] = p.data_path
    return _cache[key]


def measure(fn, params, grads, steps: int, warmup: int = 2) -> float:
    """Median wall seconds per executed step (post-compile)."""
    out = None
    for _ in range(warmup):
        out = fn(params, grads)
    jax.block_until_ready(out)
    times = []
    for _ in range(steps):
        t0 = time.perf_counter()
        out = fn(params, grads)
        jax.block_until_ready(out)
        times.append(time.perf_counter() - t0)
    return statistics.median(times)


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--quick", action="store_true",
                    help="CI perf smoke: fewer modes/steps")
    ap.add_argument("--steps", type=int, default=12)
    ap.add_argument("--layers", type=int, default=24)
    ap.add_argument("--d", type=int, default=192)
    ap.add_argument("--vocab", type=int, default=2048)
    ap.add_argument("--out", default=str(ROOT / "BENCH_step.json"))
    args = ap.parse_args()

    mesh = jax.make_mesh((2, 4), ("pod", "data"))
    tree = grad_tree(args.layers, args.d, args.vocab)
    specs = jax.tree.map(lambda _: P(), tree)
    total_bytes = sum(4 * lf.size for lf in jax.tree.leaves(tree))
    n_leaves = len(jax.tree.leaves(tree))
    steps = 5 if args.quick else args.steps
    from repro.core import topology
    fabric_topo = topology.tpu_multipod(2, 4)

    cells = [("hier", 1, None), ("hier_pipelined", 4, None),
             ("hier_pipelined", 4, "int8")]
    if not args.quick:
        cells = [("flat", 1, None)] + cells + [("hier", 1, "bf16"),
                                               ("hier_overlap", 1, None)]

    results = {}
    for mode, k, comp in cells:
        tag = mode + (f"+{comp}" if comp else "")
        paths = (("per_leaf", "packed") if mode == "flat"
                 else ("per_leaf", "legacy", "packed"))
        if mode == "hier_overlap":
            paths = ("legacy", "packed")   # overlap has no per-leaf form
        row = {"n_chunks": k, "compression": comp}
        for path in paths:
            fn = make_step(mode, k, comp, path, mesh, specs)
            t = measure(fn, tree, tree, steps)
            row[f"{path}_ms"] = round(t * 1e3, 3)
            row[f"{path}_eff_GBps"] = round(total_bytes / t / 1e9, 3)
        if "per_leaf_ms" in row:
            row["speedup_packed_vs_per_leaf"] = round(
                row["per_leaf_ms"] / row["packed_ms"], 3)
            # planner invariant: the CHOSEN data path never loses to
            # per_leaf (a per-leaf fallback scores exactly 1.0)
            emu_topo, _ = calibrate_emulated_topology(mesh)
            dp = planner_data_path(emu_topo, total_bytes, n_leaves, comp)
            chosen_ms = row["packed_ms"] if dp == "packed" \
                else row["per_leaf_ms"]
            row["planner_data_path"] = dp
            row["planner_data_path_fabric"] = planner_data_path(
                fabric_topo, total_bytes, n_leaves, comp)
            row["speedup_planner_vs_per_leaf"] = round(
                row["per_leaf_ms"] / chosen_ms, 3)
        if "legacy_ms" in row:
            row["speedup_packed_vs_legacy"] = round(
                row["legacy_ms"] / row["packed_ms"], 3)
        results[tag] = row
        print(f"{tag:24s} " + "  ".join(
            f"{p}={row.get(p + '_ms', '-')}ms" for p in
            ("per_leaf", "legacy", "packed")) +
            (f"  packed/per_leaf {row.get('speedup_packed_vs_per_leaf')}x"
             if "per_leaf_ms" in row else ""), flush=True)

    accept = results.get("hier_pipelined+int8", {}).get(
        "speedup_packed_vs_legacy", 0.0)
    planner_rows = {tag: r["speedup_planner_vs_per_leaf"]
                    for tag, r in results.items()
                    if "speedup_planner_vs_per_leaf" in r}
    planner_pass = all(v >= 1.0 for v in planner_rows.values())
    _, emu_consts = calibrate_emulated_topology(mesh)
    out = {
        "meta": {
            "devices": 8, "mesh": "pod=2 x data=4",
            "tree": {"layers": args.layers, "d": args.d,
                     "vocab": args.vocab, "n_leaves": n_leaves,
                     "grad_bytes": total_bytes},
            "steps": steps, "quick": bool(args.quick),
            "measured": "gradient sync + SGD update (comm data path "
                        "only; emulated CPU devices — relative deltas "
                        "meaningful, absolute times not)",
            "acceptance": {
                "cell": "hier_pipelined+int8",
                "metric": "speedup_packed_vs_legacy",
                "bar": 1.25,
                "value": accept,
                "pass": bool(accept >= 1.25),
                "note": "packed vs the pre-packing per-step "
                        "re-flatten/re-pad data path.  The historical "
                        "1.861x packed-vs-per-leaf figure was measured "
                        "against a per-leaf baseline inflated ~1.5x by "
                        "the pipeline-fill bug (k+2 pod rounds per "
                        "leaf) fixed in this revision; post-fix, "
                        "per-leaf on the α-cheap CPU emulation ties "
                        "packed and the planner falls back (see "
                        "planner_invariant).",
            },
            "planner_invariant": {
                "metric": "speedup_planner_vs_per_leaf",
                "bar": 1.0,
                "rule": "planner-chosen data path never loses to "
                        "per_leaf (fallback rows score 1.0)",
                "emulated_fabric_constants": emu_consts,
                "values": planner_rows,
                "pass": bool(planner_pass),
            },
        },
        "modes": results,
    }
    out_path = pathlib.Path(args.out)
    out_path.parent.mkdir(parents=True, exist_ok=True)
    out_path.write_text(json.dumps(out, indent=1) + "\n")
    print(f"\nwrote {args.out}")
    print(f"emulated-fabric constants (probed): {emu_consts}")
    print(f"acceptance hier_pipelined+int8 packed vs legacy: "
          f"{accept}x (bar 1.25x) -> {'PASS' if accept >= 1.25 else 'FAIL'}")
    print(f"planner invariant (chosen path >= per_leaf in every mode): "
          f"{planner_rows} -> {'PASS' if planner_pass else 'FAIL'}")
    # the perf-smoke CI job gates on this exit code (plus the JSON's
    # meta flags) — a bench that reports FAIL must not exit 0
    if not (accept >= 1.25 and planner_pass):
        sys.exit(1)


if __name__ == "__main__":
    main()
