"""Production training launcher: mesh + sharded step + checkpoint/restart.

Single entry point used by the examples, the FT harness and (with
``--arch``/``--steps`` flags) as a CLI. On the CPU container it runs real
training on reduced configs; on a TPU pod the same code path shards over
the production mesh (the dry-run proves those graphs compile).
"""
from __future__ import annotations

import argparse
import dataclasses
import time
from typing import Optional

import jax
import jax.numpy as jnp

from ..checkpoint.ckpt import latest_step, restore, save
from ..configs.base import InputShape, load_arch
from ..core.backend import use_compile_cache
from ..data.pipeline import DataConfig, DataIterator
from ..optim.adamw import AdamWConfig
from ..sharding.rules import ShardingRules, fitted_shardings
from ..train.step import TrainConfig, abstract_state, init_state, make_train_step


def train_loop(cfg, tcfg: TrainConfig, *, steps: int, ckpt_dir: Optional[str],
               seq_len: int, global_batch: int, mesh=None,
               rules: Optional[ShardingRules] = None, ckpt_every: int = 50,
               log_every: int = 10, seed: int = 0, log=print):
    """Returns (final_state, losses). Resumes from ckpt_dir if present."""
    shape = InputShape("train", seq_len, global_batch, "train")
    dcfg = DataConfig(vocab=cfg.vocab, seq_len=seq_len,
                      global_batch=global_batch, seed=seed)
    it = DataIterator(dcfg, cfg, shape)

    step_fn = make_train_step(cfg, tcfg, rules=rules, mesh=mesh)
    if mesh is not None and rules is not None:
        _, state_axes = abstract_state(cfg, tcfg)
        state0, _ = init_state(cfg, tcfg, jax.random.PRNGKey(seed))
        shardings = fitted_shardings(mesh, rules.for_mesh(mesh), state_axes,
                                     jax.eval_shape(lambda: state0))
        state = jax.device_put(state0, shardings)
        step_fn = jax.jit(step_fn, in_shardings=(shardings, None),
                          out_shardings=(shardings, None), donate_argnums=0)
    else:
        state, _ = init_state(cfg, tcfg, jax.random.PRNGKey(seed))
        step_fn = jax.jit(step_fn, donate_argnums=0)

    start = 0
    if ckpt_dir:
        last = latest_step(ckpt_dir)
        if last is not None:
            template = jax.eval_shape(lambda: state)
            state, extra = restore(ckpt_dir, last, template)
            it.restore(extra["data_step"])
            start = last
            log(f"[train] resumed from step {last}")

    losses = []
    t0 = time.time()
    for i in range(start, steps):
        batch = {k: jnp.asarray(v) for k, v in next(it).items()}
        state, metrics = step_fn(state, batch)
        loss = float(metrics["loss"])
        losses.append(loss)
        if log_every and (i + 1) % log_every == 0:
            rate = (i + 1 - start) / (time.time() - t0)
            log(f"[train] step {i + 1}/{steps} loss {loss:.4f} "
                f"({rate:.2f} steps/s)")
        if ckpt_dir and (i + 1) % ckpt_every == 0:
            save(ckpt_dir, i + 1, state, extra={"data_step": it.state()})
    if ckpt_dir and steps > start:
        save(ckpt_dir, steps, state, extra={"data_step": it.state()})
    return state, losses


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="smollm_360m")
    ap.add_argument("--smoke", action="store_true", default=True)
    ap.add_argument("--full", dest="smoke", action="store_false")
    ap.add_argument("--steps", type=int, default=100)
    ap.add_argument("--seq-len", type=int, default=128)
    ap.add_argument("--global-batch", type=int, default=8)
    ap.add_argument("--lr", type=float, default=3e-3)
    ap.add_argument("--qat", action="store_true")
    ap.add_argument("--quant-opt", action="store_true")
    ap.add_argument("--ckpt-dir", default="")
    ap.add_argument("--ckpt-every", type=int, default=50)
    args = ap.parse_args()
    use_compile_cache()

    mod = load_arch(args.arch)
    cfg = mod.smoke() if args.smoke else mod.full()
    tcfg = TrainConfig(
        opt=AdamWConfig(lr=args.lr, quantized_state=args.quant_opt),
        qat=args.qat, warmup_steps=max(1, args.steps // 20),
        total_steps=args.steps)
    _, losses = train_loop(cfg, tcfg, steps=args.steps,
                           ckpt_dir=args.ckpt_dir or None,
                           seq_len=args.seq_len,
                           global_batch=args.global_batch,
                           ckpt_every=args.ckpt_every)
    print(f"final loss: {losses[-1]:.4f} (start {losses[0]:.4f})")


if __name__ == "__main__":
    main()
