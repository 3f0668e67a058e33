"""End-to-end behaviour tests: train -> checkpoint -> serve; FT recovery."""
import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.checkpoint.ckpt import latest_step
from repro.configs import load_arch
from repro.launch.train import train_loop
from repro.optim.adamw import AdamWConfig
from repro.serve.step import greedy_generate
from repro.train.step import TrainConfig


def test_chip_smoke_refuses_without_tpu():
    """Without a TPU the chip smoke exits nonzero and prints no result."""
    from conftest import cpu_subproc_env
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    res = subprocess.run([sys.executable, os.path.join(root, "chip_smoke.py")],
                         capture_output=True, text=True, timeout=300,
                         env=cpu_subproc_env())
    assert res.returncode != 0
    assert '"ok"' not in res.stdout, res.stdout
    assert "found no TPU" in res.stderr, res.stderr


def test_train_then_serve(tmp_path):
    """Full lifecycle: train a smoke model, checkpoint, reload, generate."""
    cfg = load_arch("smollm_360m").smoke()
    tcfg = TrainConfig(opt=AdamWConfig(lr=3e-3), warmup_steps=2,
                       total_steps=30)
    state, losses = train_loop(cfg, tcfg, steps=12,
                               ckpt_dir=str(tmp_path), seq_len=32,
                               global_batch=4, ckpt_every=6, log_every=0)
    assert latest_step(str(tmp_path)) == 12
    assert np.mean(losses[-3:]) < np.mean(losses[:3])

    out = greedy_generate(state["params"], cfg,
                          {"tokens": jnp.ones((2, 8), jnp.int32)},
                          steps=4, max_seq=32)
    assert out.shape == (2, 4)


def test_resume_continues_not_restarts(tmp_path):
    cfg = load_arch("smollm_360m").smoke()
    tcfg = TrainConfig(opt=AdamWConfig(lr=3e-3), total_steps=30)
    train_loop(cfg, tcfg, steps=6, ckpt_dir=str(tmp_path), seq_len=32,
               global_batch=4, ckpt_every=3, log_every=0)
    # second call with more steps resumes from 6, not 0
    logs = []
    train_loop(cfg, tcfg, steps=9, ckpt_dir=str(tmp_path), seq_len=32,
               global_batch=4, ckpt_every=3, log_every=0,
               log=logs.append)
    assert any("resumed from step 6" in l for l in logs)


@pytest.mark.slow
def test_ft_crash_recovery_end_to_end(tmp_path):
    """Coordinator + injected SIGKILL: the run must finish with restarts>0."""
    run_dir = str(tmp_path / "ft")
    res = subprocess.run(
        [sys.executable, "-m", "repro.launch.ft", "--run-dir", run_dir,
         "--steps", "12", "--ckpt-every", "4", "--kill-at", "6"],
        capture_output=True, text=True, timeout=900,
        env={**os.environ, "PYTHONPATH": "src"})
    assert "restarts=1" in res.stdout, res.stdout + res.stderr
    assert "resumed from step 4" in res.stdout
    assert latest_step(os.path.join(run_dir, "ckpt")) == 12
