"""CPU rehearsal of chip_smoke.py at the qwen2.5-3b smoke size: the
one-chip phase in-process on one CPU device, the four-chip phase in a
subprocess with four virtual CPU devices, and the script's refusal to
run (or to print a result) without a TPU."""

import os
import pathlib
import shutil
import subprocess
import sys

import pytest

ROOT = pathlib.Path(__file__).resolve().parent.parent
SEQ = 64


def _env(**extra):
    return {"PYTHONPATH": str(ROOT / "src"), "JAX_PLATFORMS": "cpu",
            "PATH": os.environ.get("PATH", "/usr/bin:/bin"),
            "HOME": os.environ.get("HOME", ""), **extra}


def _run(args, cwd=ROOT, timeout=600):
    return subprocess.run([sys.executable, *args], capture_output=True,
                          text=True, timeout=timeout, env=_env(), cwd=cwd)


@pytest.fixture(scope="module")
def chip_smoke():
    sys.path.insert(0, str(ROOT))
    try:
        import chip_smoke
        yield chip_smoke
    finally:
        sys.path.remove(str(ROOT))


def test_import_touches_no_device():
    proc = _run(["-c", "import sys, chip_smoke; "
                       "print('jax' in sys.modules)"])
    assert proc.returncode == 0, proc.stderr[-2000:]
    assert proc.stdout.strip() == "False"


def test_one_chip_phase_cpu_rehearsal(chip_smoke):
    from repro.configs import get_config

    res = chip_smoke.one_chip(get_config("qwen2.5-3b", smoke=True),
                              seq=SEQ, steps=5, codec_shapes=((300, 1024),))
    losses = res["train"]["losses"]
    assert len(losses) == 5 and losses[-1] < losses[0]
    # the jnp mirror serves the CPU: no Mosaic kernel in the step
    assert res["train"]["tpu_custom_call"] == 0


def test_four_chip_phase_cpu_rehearsal():
    code = ("import jax\n"
            "jax.config.update('jax_num_cpu_devices', 4)\n"
            "import chip_smoke\n"
            "from repro.configs import get_config\n"
            f"chip_smoke.four_chip(get_config('qwen2.5-3b', smoke=True), "
            f"seq={SEQ}, steps=5)\n"
            "print('FOUR-OK')\n")
    proc = _run(["-c", code])
    assert proc.returncode == 0, (proc.stdout[-3000:], proc.stderr[-3000:])
    out = proc.stdout
    assert "FOUR-OK" in out
    assert "[compare] hier vs flat" in out
    assert "[compare] hier_pipelined+int8 vs flat" in out
    assert "lax.psum over ('pod', 'data')" in out and ": True" in out


@pytest.mark.parametrize("alone", [False, True])
def test_refuses_without_tpu(alone, tmp_path):
    """No TPU: non-zero exit and no result line, from the repo and from
    a directory that holds nothing but the script."""
    if alone:
        shutil.copy(ROOT / "chip_smoke.py", tmp_path)
        proc = _run(["chip_smoke.py"], cwd=tmp_path, timeout=120)
    else:
        proc = _run(["chip_smoke.py"], timeout=120)
    assert proc.returncode != 0
    assert '"ok"' not in proc.stdout
