"""Launcher entry-point smoke tests (subprocess, real CLI surface)."""

import os
import subprocess
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]


def _run(args, timeout=500, devices=None):
    env = dict(os.environ, PYTHONPATH=str(ROOT / "src"))
    env.pop("XLA_FLAGS", None)
    if devices is not None:
        env["XLA_FLAGS"] = f"--xla_force_host_platform_device_count={devices}"
    return subprocess.run(
        [sys.executable, "-m", *args],
        capture_output=True, text=True, env=env, timeout=timeout, cwd=ROOT,
    )


def test_train_launcher_smoke(tmp_path):
    proc = _run([
        "repro.launch.train", "--arch", "yi-6b", "--steps", "6",
        "--seq-len", "64", "--global-batch", "4",
        "--corpus-records", "400", "--ckpt-every", "3",
        "--workdir", str(tmp_path / "run"),
    ])
    assert proc.returncode == 0, proc.stderr[-2000:]
    assert "done: 6 steps" in proc.stdout
    assert (tmp_path / "run" / "ckpt").exists()


def test_serve_launcher_smoke():
    proc = _run([
        "repro.launch.serve", "--arch", "yi-6b",
        "--max-new-tokens", "4", "--max-len", "64",
        "--prompts", "InChI=1S/C4",
    ])
    assert proc.returncode == 0, proc.stderr[-2000:]
    assert "tok/s" in proc.stdout


def test_serve_launcher_continuous_on_a_mesh():
    """``--continuous --mesh 1x4`` serves through ContinuousEngine on four
    (fake CPU) devices instead of refusing."""
    proc = _run([
        "repro.launch.serve", "--arch", "yi-6b", "--continuous",
        "--mesh", "1x4", "--max-new-tokens", "4", "--max-len", "64",
        "--max-slots", "4", "--prompts", "InChI=1S/C4", "InChI=1S/CH4/h1H4",
    ], devices=4)
    assert proc.returncode == 0, proc.stderr[-2000:]
    assert "mesh 1x4" in proc.stdout
    assert proc.stdout.count("tok/s") == 2
