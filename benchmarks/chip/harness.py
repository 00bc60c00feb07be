"""One run of one cell of the chip benchmark.

A cell is one entry of ``workloads`` in ``BENCHMARK.json``: a model
configuration (``configs/<config>.json``) under a traffic mix
(``traffic/<traffic>.json``), with the limits of its correctness check
(``limits/<cell>.json``).  Its per-layer metrics are read by
``metrics/<metric>.py``.  A configuration may name what it brings of its
own (``resolve``): fields of the program's ``ModelConfig`` under
``"program"``, its plain reference module under ``"reference"`` and its
FLOP count under ``"flops"``.  Everything is found by name, so a new
cell, configuration, traffic mix or metric is a new file and no edit
here.

A run builds the program's training step through its library path
(``Model`` -> ``make_train_step``) for the cell's mesh and compiles it;
makes the weights and Adam state on the device from the seed; drives
the step through its first three steps, whose readings the correctness
check compares with the plain reference; then measures a window of
``seconds`` in a closed loop that keeps one step in flight, each step
on a fresh batch from the benchmark's own prefetch thread.  With a
trace, a few steps are traced first, in a segment of their own, and
after the window every compiled instruction is given the program's
named scope, so that the readers get each scope's device time.  After
the window the program's state is freed and the configuration's
reference repeats the first three steps.
"""

from __future__ import annotations

import contextlib
import dataclasses
import gc
import importlib.util
import json
import math
import os
import pathlib
import queue
import re
import resource
import shutil
import statistics
import sys
import tempfile
import threading
import time
import types

import compare
import peaks as peaks_lib
import scopemap
import tokens
import tracereduce

BENCH = pathlib.Path(__file__).resolve().parent
REPO = BENCH.parents[1]
FIRST_STEPS = 3
SPANS = ("batch_wait", "dispatch", "wait_metrics")
WINDOW = "traced_window"
GIB = 1 << 30
# what a configuration's reference and FLOP modules provide
REFERENCE_API = ("abstract_params", "init_params", "seed_key",
                 "leaf_norms", "change_norms", "LOWER", "Reference")
FLOPS_API = ("train_flops_per_token",)
_STEM = re.compile(r"[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}")


class SetupError(RuntimeError):
    """The cell cannot be run as its files describe it."""


class NoChip(SetupError):
    """JAX found no TPU, or fewer chips than the cell asks for."""


# ------------------------------------------------------------ the cell --

@dataclasses.dataclass
class Cell:
    name: str
    chips: int
    config: dict
    traffic: dict
    limits: dict
    end_to_end: list
    per_layer: list        # [(metric entry, path of its reader)]
    home: pathlib.Path     # the benchmark's own directory

    @property
    def rows(self) -> int:
        """Sequences in one global step."""
        mesh = self.traffic["mesh"]
        return self.traffic["seqs_per_chip"] * self.chips // mesh["model"]

    @property
    def tokens_per_step(self) -> int:
        return self.rows * self.traffic["seq_len"]


def _read_json(path: pathlib.Path, what: str) -> dict:
    if not path.is_file():
        raise SetupError(f"{what}: no file {path}")
    return json.loads(path.read_text())


def load_cell(root: pathlib.Path, workload: str) -> Cell:
    """Resolve a workload of ``root/BENCHMARK.json`` to its files."""
    bench = _read_json(root / "BENCHMARK.json", "benchmark")
    cells = {w["name"]: w for w in bench["workloads"]}
    if workload not in cells:
        raise SetupError(f"unknown workload {workload!r}; known: "
                         f"{sorted(cells)}")
    w = cells[workload]
    configs = {c["name"]: c for c in bench["configs"]}
    if w["config"] not in configs:
        raise SetupError(f"{workload}: unknown config {w['config']!r}")
    home = root / bench["paths"][0]

    def covers(metric: dict) -> bool:
        return workload in metric.get("workloads", [workload])

    e2e = [m for m in bench["end_to_end"] if covers(m)]
    names = {m["name"] for m in e2e}
    layer = []
    for m in bench["per_layer"]:
        if covers(m) and m["moves"] in names:
            reader = home / "metrics" / f"{m['name']}.py"
            if not reader.is_file():
                raise SetupError(f"metric {m['name']}: no reader {reader}")
            layer.append((m, reader))
    return Cell(
        name=workload, chips=w["chips"],
        config=_read_json(root / configs[w["config"]]["file"], "config"),
        traffic=_read_json(home / "traffic" / f"{w['traffic']}.json",
                           "traffic"),
        limits=_read_json(home / "limits" / f"{workload}.json", "limits"),
        end_to_end=e2e, per_layer=layer, home=home)


def load_module(path: pathlib.Path, kind: str) -> types.ModuleType:
    """The module in ``path``, loaded by its file path."""
    name = f"chip_{kind}_{path.stem.replace('.', '_').replace('-', '_')}"
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def load_reader(path: pathlib.Path):
    return load_module(path, "metric").read


# --------------------------------------------------------- the program --

# configuration keys (as published) -> ModelConfig fields of the program
_FIELDS = {"num_hidden_layers": "n_layers", "hidden_size": "d_model",
           "num_attention_heads": "n_heads",
           "num_key_value_heads": "n_kv_heads",
           "intermediate_size": "d_ff", "vocab_size": "vocab_size",
           "tie_word_embeddings": "tie_embeddings",
           "rope_theta": "rope_theta",
           "max_position_embeddings": "max_seq", "norm": "norm",
           "qkv_bias": "qkv_bias"}


def model_config(cfg: dict):
    """The program's ``ModelConfig`` for a configuration file: its
    published keys translated by ``_FIELDS``, then the fields its
    ``"program"`` section sets, which win; ``family`` is ``"dense"``
    unless that section says otherwise."""
    import jax.numpy as jnp
    from repro.configs.base import ModelConfig

    if cfg.get("norm_eps", 1e-5) != 1e-5:
        raise SetupError("the program's norms use eps 1e-5; the "
                         f"configuration states {cfg['norm_eps']}")
    program = cfg.get("program", {})
    fields = {f.name for f in dataclasses.fields(ModelConfig)}
    unknown = sorted(set(program) - fields)
    if unknown:
        raise SetupError(f"{cfg['name']}: 'program' sets {unknown}, no "
                         f"field of the program's ModelConfig")
    # the precision is the configuration's own "dtype", which the
    # control lowers; the name is the file's
    fixed = sorted({"name", "dtype"} & set(program))
    if fixed:
        raise SetupError(f"{cfg['name']}: 'program' may not set {fixed}")
    kw = {field: cfg[key] for key, field in _FIELDS.items() if key in cfg}
    kw = {"family": "dense", **kw, **program}
    return ModelConfig(name=cfg["name"], dtype=jnp.dtype(cfg["dtype"]), **kw)


@dataclasses.dataclass(frozen=True)
class Parts:
    """What a configuration runs with: the program's ``ModelConfig``, the
    plain reference module (``REFERENCE_API``; ``reference.py`` says what
    each name does) and the module that counts the model's FLOPs."""
    model: object
    reference: types.ModuleType
    flops: types.ModuleType


def _part(home: pathlib.Path, cfg: dict, key: str, api: tuple):
    stem = cfg.get(key, key)
    if not isinstance(stem, str) or not _STEM.fullmatch(stem):
        raise SetupError(f"{cfg['name']}: {key!r} names no module: {stem!r}")
    path = home / f"{stem}.py"
    if not path.is_file():
        raise SetupError(f"{cfg['name']}: no {key} module {path}")
    mod = load_module(path, key)
    missing = [a for a in api if not hasattr(mod, a)]
    if missing:
        raise SetupError(f"{cfg['name']}: {key} module {path} lacks "
                         f"{missing}")
    return mod


def resolve(home: pathlib.Path, cfg: dict) -> Parts:
    """The parts a configuration names, found under ``home``: its
    ``"program"`` fields, its ``"reference"`` module (default
    ``reference``) and its ``"flops"`` module (default ``flops``)."""
    return Parts(model=model_config(cfg),
                 reference=_part(home, cfg, "reference", REFERENCE_API),
                 flops=_part(home, cfg, "flops", FLOPS_API))


def opt_config(traffic: dict):
    from repro.train.optimizer import OptConfig
    return OptConfig(**traffic["optimizer"])


class Feed:
    """The benchmark's bounded prefetch: a thread makes step ``i``'s batch
    from (seed, i) and ``device_put``s it with the step's batch sharding."""

    def __init__(self, cell: Cell, seed: int, sharding, depth: int = 2):
        t = cell.traffic
        self._args = (cell.rows, t["seq_len"], cell.config["vocab_size"],
                      t["generator"]["zipf_a"])
        self.seed, self._sharding = seed, sharding
        self._q: queue.Queue = queue.Queue(maxsize=depth)
        self._stop = threading.Event()
        self.error: BaseException | None = None
        self._thread = threading.Thread(target=self._run, daemon=True)
        self._thread.start()

    def _run(self):
        import jax
        step = 0
        try:
            while not self._stop.is_set():
                b = tokens.batch(self.seed, step, *self._args)
                dev = {k: jax.device_put(v, self._sharding)
                       for k, v in b.items()}
                while not self._stop.is_set():
                    try:
                        self._q.put(dev, timeout=0.1)
                        break
                    except queue.Full:
                        continue
                step += 1
        except BaseException as e:  # surfaced by get()
            self.error = e

    def get(self) -> dict:
        while True:
            try:
                return self._q.get(timeout=1.0)
            except queue.Empty:
                if self.error is not None or not self._thread.is_alive():
                    raise RuntimeError("batch feed stopped") from self.error

    def close(self) -> None:
        self._stop.set()
        while True:
            try:
                self._q.get_nowait()
            except queue.Empty:
                break
        self._thread.join(timeout=30.0)
        if self._thread.is_alive():
            raise RuntimeError("batch feed thread did not stop")


class Program:
    """The system under test for one cell: its mesh, the compiled
    training step, and the state the step is driven with."""

    def __init__(self, cell: Cell, devices):
        import jax
        from jax.sharding import NamedSharding, PartitionSpec as P

        from repro.launch.mesh import runtime_for_mesh
        from repro.models import Model
        from repro.train import TrainConfig, make_train_step
        from repro.train.optimizer import AdamState, adam_init

        t = cell.traffic
        m = t["mesh"]
        self.cell = cell
        self.parts = resolve(cell.home, cell.config)
        reference = self.parts.reference
        self.mesh = jax.make_mesh((m["pod"], m["data"], m["model"]),
                                  ("pod", "data", "model"), devices=devices)
        model = Model(self.parts.model, runtime_for_mesh(self.mesh))
        tcfg = TrainConfig(comm_mode=t["comm_mode"],
                           dcn_compression=t["compression"],
                           packed=t["packed"], n_chunks=t["n_chunks"],
                           opt=opt_config(t))
        builder, _ = make_train_step(model, tcfg, mesh=self.mesh)
        pshape = jax.eval_shape(model.init, jax.random.key(0))
        mine = reference.abstract_params(cell.config)
        if jax.tree.structure(pshape) != jax.tree.structure(mine) or any(
                (a.shape, a.dtype) != (b.shape, b.dtype) for a, b in
                zip(jax.tree.leaves(pshape), jax.tree.leaves(mine))):
            raise SetupError("the program's parameter tree differs from the "
                             "benchmark's for this configuration")
        step_fn, boot = builder(pshape)
        if boot is not None:
            raise SetupError(f"comm_mode {t['comm_mode']!r} keeps a ZeRO "
                             f"state the benchmark does not build")
        named = lambda s: NamedSharding(self.mesh, s)  # noqa: E731
        psh = jax.tree.map(named, model.param_specs(pshape),
                           is_leaf=lambda s: isinstance(s, P))
        osh = AdamState(psh, psh, named(P()))
        self.batch_sharding = named(P(model.rt.dp_axes or None))
        cfg = cell.config

        def make(key):
            p = reference.init_params(cfg, key)
            return p, adam_init(p)

        self._make = jax.jit(make, out_shardings=(psh, osh))
        self._p0 = jax.jit(lambda k: reference.init_params(cfg, k),
                           out_shardings=psh)
        self._norms = jax.jit(reference.leaf_norms)
        self._change = jax.jit(reference.change_norms)
        abstract = lambda tree, sh: jax.tree.map(  # noqa: E731
            lambda a, s: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=s),
            tree, sh)
        opt_shape = jax.eval_shape(adam_init, pshape)
        rows, seq = cell.rows, t["seq_len"]
        bshape = {k: jax.ShapeDtypeStruct((rows, seq), jax.numpy.int32,
                                          sharding=self.batch_sharding)
                  for k in ("tokens", "labels")}
        # the module before optimisation names the scope of each
        # collective that the compiler rewrites without its metadata
        self.lowered = step_fn.lower(abstract(pshape, psh),
                                     abstract(opt_shape, osh), bshape)
        self.compiled = self.lowered.compile()
        self.step = self.compiled

    def labels(self) -> dict:
        """{instruction: named scope} of the compiled step
        (``scopemap.labels``); the instructions of no scope left out."""
        return scopemap.labels(
            self.compiled.as_text(),
            self.lowered.as_text(dialect="hlo", debug_info=True))

    def memory_analysis_bytes(self) -> int | None:
        """Per-device bytes the compiled step needs: arguments + outputs
        - aliased + temp (None where the backend gives no analysis)."""
        ma = self.compiled.memory_analysis()
        if ma is None:
            return None
        return (ma.argument_size_in_bytes + ma.output_size_in_bytes
                - ma.alias_size_in_bytes + ma.temp_size_in_bytes)

    def state(self, seed: int):
        return self._make(self.parts.reference.seed_key(seed))

    def first_steps(self, state, feed: Feed, opt_cfg: dict,
                    n: int = FIRST_STEPS):
        """The first ``n`` steps through the window's own call and feed;
        returns the state after them and their readings: each step's
        loss, each leaf's norm of the first gradient before clipping and
        each leaf's change over the ``n`` steps.  The gradient is read as
        the optimizer got it, from its first moment (mu / (1 - b1) after
        one step), and scaled back by the clip factor of the global norm
        the step reports, so that a gradient wrong by a uniform factor is
        not divided away by the clipping."""
        params, opt = state
        losses, grad_norms = [], None
        for i in range(n):
            params, opt, m = self.step(params, opt, feed.get())
            losses.append(float(m["loss"]))
            if i == 0:
                clip = min(1.0, opt_cfg["grad_clip"]
                           / (float(m["grad_norm"]) + 1e-9))
                grad_norms = {k: float(v) / (1.0 - opt_cfg["b1"]) / clip
                              for k, v in self._norms(opt.mu).items()}
        p0 = self._p0(self.parts.reference.seed_key(feed.seed))
        change = {k: float(v) for k, v in self._change(params, p0).items()}
        del p0
        return (params, opt), {"losses": losses, "grad_norms": grad_norms,
                               "change_norms": change}


def loop(step, state, feed: Feed, *, steps: int | None = None,
         seconds: float | None = None, annotate: bool = False):
    """Closed loop with one step in flight: dispatch step i+1 with its
    batch before waiting on step i.  Stops dispatching after ``steps``
    steps or once ``seconds`` have passed, then waits for the last.
    Returns the state and the record of the loop on the host clock."""
    import jax

    def span(name):
        if annotate:
            return jax.profiler.TraceAnnotation(name)
        return contextlib.nullcontext()

    params, opt = state
    rec = {"start": time.perf_counter(), "completions": [], "losses": [],
           "spans": {k: [] for k in SPANS}, "attempted": 0}

    def timed(name, fn):
        t = time.perf_counter()
        with span(name):
            out = fn()
        rec["spans"][name].append(time.perf_counter() - t)
        return out

    def finish(m):
        timed("wait_metrics", lambda: m["loss"].block_until_ready())
        rec["completions"].append(time.perf_counter())
        rec["losses"].append(float(m["loss"]))

    pending = None
    while True:
        batch = timed("batch_wait", feed.get)
        params, opt, m = timed("dispatch",
                               lambda: step(params, opt, batch))
        rec["attempted"] += 1
        if pending is not None:
            finish(pending)
        pending = m
        if steps is not None and rec["attempted"] >= steps:
            break
        if seconds is not None and \
                time.perf_counter() - rec["start"] >= seconds:
            break
    finish(pending)
    return (params, opt), rec


def window_stats(rec: dict, tokens_per_step: int) -> dict:
    c = rec["completions"]
    intervals = [b - a for a, b in zip([rec["start"]] + c[:-1], c)]
    seconds = c[-1] - rec["start"]
    return {"steps": len(c), "seconds": seconds,
            "tokens_per_s": len(c) * tokens_per_step / seconds,
            "step_s": intervals, "spans": rec["spans"]}


def percentile(values: list, q: float) -> float:
    """Linear-interpolated percentile (``q`` in 0..100)."""
    s = sorted(values)
    pos = (len(s) - 1) * q / 100.0
    lo = math.floor(pos)
    hi = min(lo + 1, len(s) - 1)
    return s[lo] + (s[hi] - s[lo]) * (pos - lo)


# ---------------------------------------------------------------- a run --

def check_devices(chips: int):
    import jax
    devices = jax.devices()
    if devices[0].platform != "tpu":
        raise NoChip(f"no TPU found (JAX platform {devices[0].platform!r})")
    if len(devices) < chips:
        raise NoChip(f"the cell asks for {chips} chips, JAX found "
                     f"{len(devices)}")
    return devices[:chips]


def enable_compile_cache(root: pathlib.Path) -> None:
    """JAX's persistent compilation cache at the fixed ``<root>/.jax_cache``
    unless ``JAX_COMPILATION_CACHE_DIR`` names one; every program cached."""
    import jax
    if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        jax.config.update("jax_compilation_cache_dir",
                          str(root / ".jax_cache"))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)


def run(cell: Cell, seed: int, seconds: float, trace: bool, devices,
        t_start: float, log=print) -> dict:
    """One run; returns the result line as a dict."""
    t = cell.traffic
    prog = Program(cell, devices)
    feed = Feed(cell, seed, prog.batch_sharding)
    try:
        state, readings = prog.first_steps(prog.state(seed), feed,
                                           t["optimizer"])
        # what tracing and compiling left behind is collected here, in
        # set-up, and the collector stays off in the window: a full
        # collection over it stalls the host for seconds
        gc.collect()
        gc.freeze()
        setup_s = time.perf_counter() - t_start
        tr = reduced = None
        if trace:
            state, tr, reduced = traced_segment(prog, state, feed,
                                                t["trace_steps"])
        gc.disable()
        before = resource.getrusage(resource.RUSAGE_SELF)
        try:
            state, rec = loop(prog.step, state, feed, seconds=seconds)
        finally:
            gc.enable()
        after = resource.getrusage(resource.RUSAGE_SELF)
        del state
    finally:
        feed.close()
    win = window_stats(rec, cell.tokens_per_step)
    ms = sorted(x * 1e3 for x in win["step_s"])
    log(f"[window] {win['steps']} steps in {win['seconds']:.3f} s; step ms "
        f"min {ms[0]:.2f} median {statistics.median(ms):.2f} p90 "
        f"{percentile(ms, 90):.2f} max {ms[-1]:.2f}; setup {setup_s:.2f} s")
    longest = max(range(len(win["step_s"])), key=win["step_s"].__getitem__)
    log(f"[window] longest interval {win['step_s'][longest] * 1e3:.2f} ms "
        f"ends at completion {longest}; longest span of each kind: " + ", ".join(
            f"{k} {max(v) * 1e3:.2f} ms at {v.index(max(v))}"
            for k, v in win["spans"].items() if v))
    # the process's CPU seconds in the window: a host stall with them
    # flat was spent off the CPU
    log(f"[window] host CPU: user {after.ru_utime - before.ru_utime:.3f} s, "
        f"system {after.ru_stime - before.ru_stime:.3f} s")
    peak_used = max((d.memory_stats() or {}).get("peak_bytes_in_use", 0)
                    for d in devices)
    compiled = prog.memory_analysis_bytes() or 0
    peak = max(peak_used, compiled)
    log(f"[memory] peak_bytes_in_use {peak_used} B, compiled step "
        f"{compiled} B: peak_hbm_gib from "
        f"{'the compiled step' if compiled >= peak_used else 'peak_bytes_in_use'}")
    phases = None
    if reduced is not None:
        t0 = time.perf_counter()
        phases = tracereduce.by_phase(tr, prog.labels(), WINDOW,
                                      t["trace_steps"])["phase_s_per_step"]
        log(f"[scopes] labelled and reduced in "
            f"{time.perf_counter() - t0:.2f} s; ms a step: " + ", ".join(
                f"{k} {1e3 * v:.3f}" for k, v in phases.items()))
    parts = prog.parts
    del prog, tr

    ref = parts.reference.Reference(cell.config, t["optimizer"], devices)
    batches = [tokens.batch(seed, s, cell.rows, t["seq_len"],
                            cell.config["vocab_size"], t["generator"]["zipf_a"])
               for s in range(FIRST_STEPS)]
    ref_readings = ref.run(seed, batches)
    numbers = compare.numbers(readings, ref_readings)
    for line in compare.describe(readings, ref_readings):
        log(f"[compare] {line}")
    correct, checks = compare.judge(numbers, cell.limits)
    losses = rec["losses"]
    failed = sum(1 for x in losses if not math.isfinite(x))
    correct = correct and failed == 0

    dev = devices[0]
    result = {"correct": correct, "attempted": rec["attempted"],
              "failed": failed,
              "device": {"platform": dev.platform, "kind": dev.device_kind,
                         "count": len(devices), "memory_peak_bytes": peak}}
    if trace:
        ctx = metric_context(cell, parts, win, reduced, phases,
                             dev.device_kind)
        metrics = {}
        for m, path in cell.per_layer:
            value = load_reader(path)(ctx)
            if value is not None:
                metrics[m["name"]] = {"value": value, "unit": m["unit"]}
        result["metrics"] = metrics
        if reduced is not None:
            result["device"].update(busy_s=reduced["busy_s"],
                                    window_s=reduced["window_s"])
            result["breakdown"] = {"device_ops": reduced["device_ops"],
                                   "idle_gaps": reduced["idle_gaps"]}
    else:
        values = {"tokens_per_s": win["tokens_per_s"],
                  "step_ms_p90": percentile(win["step_s"], 90) * 1e3,
                  "peak_hbm_gib": peak / GIB, "setup_s": setup_s}
        result["metrics"] = {m["name"]: {"value": values[m["name"]],
                                         "unit": m["unit"]}
                             for m in cell.end_to_end}
    result["checks"] = checks
    return result


def traced_segment(prog: Program, state, feed: Feed, steps: int):
    """Trace ``steps`` steps of the loop in a segment of their own;
    returns the state, the loaded trace and its reduction (both None
    when the profiler wrote no trace; the reduction None when the trace
    holds no device op)."""
    import jax

    tdir = tempfile.mkdtemp(prefix="chip-trace-")
    try:
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        opts.host_tracer_level = 1
        jax.profiler.start_trace(tdir, profiler_options=opts)
        try:
            with jax.profiler.TraceAnnotation(WINDOW):
                state, _ = loop(prog.step, state, feed, steps=steps,
                                annotate=True)
        finally:
            jax.profiler.stop_trace()
        files = sorted(pathlib.Path(tdir).rglob("*.xplane.pb"))
        if not files:
            return state, None, None
        tr = tracereduce.load(str(files[-1]), SPANS + (WINDOW,))
        return state, tr, tracereduce.reduce(tr, WINDOW, steps)
    finally:
        shutil.rmtree(tdir, ignore_errors=True)


def metric_context(cell: Cell, parts: Parts, win: dict, reduced, phases,
                   device_kind: str):
    """What the per-layer readers read: the measured window, the reduced
    trace and each named scope's device seconds a step (``a/b`` counting
    for ``a`` too; both None when the trace holds no device op), the
    cell's configuration and traffic, and the yardstick's FLOPs and
    peaks."""
    return {"window": win,
            "trace": reduced,
            "phases": phases,
            "config": cell.config,
            "traffic": cell.traffic,
            "chips": cell.chips,
            "flops_per_token": parts.flops.train_flops_per_token(
                cell.config, cell.traffic["seq_len"]),
            "peak": peaks_lib.peaks(device_kind)}


def checks_text(checks: dict) -> list:
    return [f"check {k}: {v['value']!r} limit {v['limit']!r}"
            for k, v in checks.items()]


def main(argv=None, root: pathlib.Path = REPO, require_chip: bool = True,
         t_start: float | None = None) -> int:
    import argparse

    t_start = time.perf_counter() if t_start is None else t_start
    ap = argparse.ArgumentParser(description="Run one benchmark cell once.")
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    if args.seed < 0:
        ap.error("--seed must be a non-negative integer")

    def log(msg):
        print(msg, file=sys.stderr, flush=True)

    try:
        cell = load_cell(root, args.workload)
        import jax
        if require_chip:
            devices = check_devices(cell.chips)
            peaks_lib.peaks(devices[0].device_kind)
        else:
            devices = jax.devices()[:cell.chips]
            if len(devices) < cell.chips:
                raise NoChip(f"{cell.chips} devices asked, "
                             f"{len(devices)} found")
        enable_compile_cache(root)
        result = run(cell, args.seed, args.seconds, bool(args.trace),
                     devices, t_start, log)
    except (SetupError, peaks_lib.UnknownDevice) as e:
        log(f"run.py: {e}; nothing was measured")
        return 2
    for line in checks_text(result["checks"]):
        log(line)
    print(json.dumps(result), flush=True)
    return 0
