"""The benchmark as data: every name in BENCHMARK.json resolves to its
files, files dropped in are found by name, and a run without a TPU
exits non-zero and prints no result."""

import json
import os
import pathlib
import shutil
import subprocess
import sys

import pytest

import harness

HERE = pathlib.Path(__file__).resolve().parent
REPO = HERE.parents[1]
BENCH = json.loads((REPO / "BENCHMARK.json").read_text())


@pytest.mark.parametrize("workload", [w["name"] for w in BENCH["workloads"]])
def test_every_cell_resolves(workload):
    cell = harness.load_cell(REPO, workload)
    assert cell.chips in (1, 4)
    mesh = cell.traffic["mesh"]
    assert mesh["pod"] * mesh["data"] * mesh["model"] == cell.chips
    assert set(cell.limits) == {"loss_gap", "grad_norm_gap",
                                "update_norm_gap"}
    names = {m["name"] for m in cell.end_to_end}
    assert {"tokens_per_s", "step_ms_p90", "peak_hbm_gib",
            "setup_s"} <= names
    for m, reader in cell.per_layer:
        assert m["moves"] in names
        assert callable(harness.load_reader(reader))
    sync = {m["name"] for m, _ in cell.per_layer if m["layer"] ==
            "gradient sync"}
    assert sync == ({"sync_collective_ms", "sync_exposed_ms"}
                    if cell.chips == 4 else set())


@pytest.mark.parametrize("path", sorted((HERE / "configs").glob("*.json")),
                         ids=lambda p: p.stem)
def test_config_files_state_their_cut(path):
    """Each file holds the configuration as it is run, its source, the
    keys cut from the published model, the sizes assumed and the
    deployment; apart from the cut it matches the program's registry."""
    from repro.configs import get_config

    cfg = json.loads(path.read_text())
    assert cfg["name"] == path.stem
    for entry in BENCH["configs"]:
        if entry["name"] == cfg["name"]:
            assert entry["file"] == f"benchmarks/chip/configs/{path.name}"
            assert entry["reduced"] == cfg["reduced"]
    assert set(cfg["published"]) == set(cfg["reduced"])
    assert cfg["assumed"] and cfg["deployment"] and cfg["source"]
    mine = harness.model_config(cfg)
    full = get_config(cfg["arch"])
    assert full.n_layers == cfg["published"]["num_hidden_layers"]
    assert mine.n_layers < full.n_layers
    for f in ("d_model", "n_heads", "n_kv_heads", "d_ff", "vocab_size",
              "norm", "qkv_bias", "tie_embeddings", "rope_theta"):
        assert getattr(mine, f) == getattr(full, f), f


def _copy_bench(dst: pathlib.Path) -> dict:
    shutil.copy(REPO / "BENCHMARK.json", dst)
    shutil.copytree(HERE, dst / "benchmarks" / "chip",
                    ignore=shutil.ignore_patterns("__pycache__"))
    return json.loads((dst / "BENCHMARK.json").read_text())


def test_dropped_in_files_found_by_name(tmp_path):
    bench = _copy_bench(tmp_path)
    home = tmp_path / "benchmarks" / "chip"
    cfg = json.loads((home / "configs" / "olmo-1b-fp32.json").read_text())
    cfg.update(name="olmo-1b-deep", num_hidden_layers=14)
    (home / "configs" / "olmo-1b-deep.json").write_text(json.dumps(cfg))
    traffic = json.loads((home / "traffic" / "seq2k-b2.1chip.json")
                         .read_text())
    traffic["seq_len"] = 1024
    (home / "traffic" / "seq1k-b2.1chip.json").write_text(
        json.dumps(traffic))
    (home / "limits" / "olmo-1b-deep.seq1k-b2.1chip.json").write_text(
        json.dumps({"loss_gap": 1, "grad_norm_gap": 1,
                    "update_norm_gap": 1}))
    (home / "metrics" / "steps_in_window.py").write_text(
        "def read(ctx):\n    return ctx['window']['steps']\n")
    bench["configs"].append({"name": "olmo-1b-deep", "source": "x",
                             "file": "benchmarks/chip/configs/"
                                     "olmo-1b-deep.json",
                             "reduced": [], "why": "x"})
    bench["workloads"].append({"name": "olmo-1b-deep.seq1k-b2.1chip",
                               "config": "olmo-1b-deep",
                               "traffic": "seq1k-b2.1chip", "chips": 1,
                               "why": "x"})
    bench["per_layer"].append({"name": "steps_in_window", "unit": "steps",
                               "better": "higher", "source": "host_clock",
                               "layer": "driver", "moves": "tokens_per_s"})
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(bench))

    cell = harness.load_cell(tmp_path, "olmo-1b-deep.seq1k-b2.1chip")
    assert cell.config["num_hidden_layers"] == 14
    assert cell.traffic["seq_len"] == 1024
    assert cell.tokens_per_step == 2 * 1024
    readers = {m["name"]: r for m, r in cell.per_layer}
    assert harness.load_reader(readers["steps_in_window"])(
        {"window": {"steps": 7}}) == 7
    # a metric with no "workloads" key reaches every cell that reports
    # the metric it moves, those already there too
    old = harness.load_cell(tmp_path, "olmo-1b-fp32.seq2k-b2.1chip")
    assert "steps_in_window" in {m["name"] for m, _ in old.per_layer}


def test_missing_files_are_setup_errors(tmp_path):
    _copy_bench(tmp_path)
    with pytest.raises(harness.SetupError):
        harness.load_cell(tmp_path, "no-such-cell")
    (tmp_path / "benchmarks/chip/traffic/seq2k-b2.1chip.json").unlink()
    with pytest.raises(harness.SetupError):
        harness.load_cell(tmp_path, "olmo-1b-fp32.seq2k-b2.1chip")


def _run(cwd, workload="olmo-1b-fp32.seq2k-b2.1chip"):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("PYTHONPATH", None)
    return subprocess.run(
        [sys.executable, "benchmarks/chip/run.py", "--workload", workload,
         "--seed", "4294967311", "--seconds", "1", "--trace", "0"],
        cwd=cwd, env=env, capture_output=True, text=True, timeout=300)


def test_no_tpu_exits_without_result():
    p = _run(REPO)
    assert p.returncode != 0
    assert p.stdout == ""
    assert "no TPU" in p.stderr


def test_bare_benchmark_directory_exits_without_result(tmp_path):
    # a directory with only BENCHMARK.json and the benchmark's own files
    _copy_bench(tmp_path)
    p = _run(tmp_path)
    assert p.returncode != 0
    assert p.stdout == ""
