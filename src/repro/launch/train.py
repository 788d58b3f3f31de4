"""Training launcher.

    PYTHONPATH=src python -m repro.launch.train --arch smollm-135m --reduced \
        --steps 50 --batch 8 --seq 128 --ckpt-dir /tmp/ckpt [--resume]

On this CPU container the launcher runs reduced configs on a 1-device mesh;
on a pod the same entrypoint picks up ``make_production_mesh()`` and the
sharding trees from parallel/sharding.py (exactly the dry-run's jit
configuration, but with real arrays).
"""
from __future__ import annotations

import argparse
import time
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

from repro.checkpoint import checkpoint as ckpt_lib
from repro.configs import get_config
from repro.data.pipeline import DataConfig, SyntheticTokens
from repro.launch.compile_cache import init_compile_cache
from repro.models.frontend import synth_image_embeds
from repro.models.transformer import CallConfig, build_model
from repro.runtime.fault_tolerance import Supervisor
from repro.train.optimizer import OptConfig, init_opt_state
from repro.train.train_step import make_train_step


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="smollm-135m")
    ap.add_argument("--reduced", action="store_true", help="smoke-size config")
    ap.add_argument("--steps", type=int, default=50)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=128)
    ap.add_argument("--lr", type=float, default=3e-3)
    ap.add_argument("--schedule", default="wsd")
    ap.add_argument("--accum", type=int, default=1)
    ap.add_argument("--ckpt-dir", default="")
    ap.add_argument("--ckpt-every", type=int, default=20)
    ap.add_argument("--resume", action="store_true")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--log-every", type=int, default=10)
    args = ap.parse_args(argv)
    init_compile_cache()

    cfg = get_config(args.arch)
    if args.reduced:
        cfg = cfg.reduced()
    model = build_model(cfg, CallConfig(remat="block", dp_size=1))
    ocfg = OptConfig(lr=args.lr, schedule=args.schedule, warmup_steps=max(args.steps // 10, 1),
                     total_steps=args.steps)

    data = SyntheticTokens(DataConfig(
        vocab_size=cfg.vocab_size, seq_len=args.seq, global_batch=args.batch,
        seed=args.seed, num_codebooks=cfg.num_codebooks,
    ))
    img_key = jax.random.PRNGKey(args.seed + 1)

    def batch_at(step):
        b = {k: jnp.asarray(v) for k, v in data.batch_at(step).items()}
        if cfg.family == "vlm":
            b["image_embeds"] = synth_image_embeds(
                jax.random.fold_in(img_key, step), cfg, args.batch
            )
        return b

    step_fn = jax.jit(make_train_step(model, ocfg, accum_steps=args.accum), donate_argnums=0)

    start_step = 0
    key = jax.random.PRNGKey(args.seed)
    params = model.init(key)
    state = {"params": params, "opt": init_opt_state(params, ocfg), "rng": key}
    if args.resume and args.ckpt_dir:
        latest = ckpt_lib.latest_step(args.ckpt_dir)
        if latest is not None:
            state, manifest = ckpt_lib.restore(args.ckpt_dir, state)
            start_step = manifest["step"]
            print(f"resumed from step {start_step}")

    losses = []

    def train_fn(state, batch):
        state, metrics = step_fn(state, batch)
        return state, metrics

    def save_fn(step, st):
        if args.ckpt_dir:
            ckpt_lib.save(args.ckpt_dir, step, jax.tree.map(np.asarray, st))

    def restore_fn():
        st, man = ckpt_lib.restore(args.ckpt_dir, state)
        return st, man["step"]

    sup = Supervisor(save_fn=save_fn, restore_fn=restore_fn, ckpt_every=args.ckpt_every)
    t0 = time.time()
    step = start_step
    while step < args.steps:
        state, metrics = train_fn(state, batch_at(step))
        step += 1
        if args.ckpt_dir and step % args.ckpt_every == 0:
            save_fn(step, state)
        if step % args.log_every == 0 or step == args.steps:
            loss = float(metrics["loss"])
            losses.append(loss)
            dt = (time.time() - t0) / max(step - start_step, 1)
            print(f"step {step:5d} loss {loss:8.4f} lr {float(metrics['lr']):.2e} "
                  f"gnorm {float(metrics['grad_norm']):7.3f} {dt*1e3:7.1f} ms/step", flush=True)
    return losses


if __name__ == "__main__":
    main()
