"""Training launcher.

    PYTHONPATH=src python -m repro.launch.train --arch deepseek-7b \
        --reduced --steps 50 [--resume] [--microbatches 2]

On this CPU container only reduced configs are runnable; on a real
TPU slice the same entry point builds the production mesh, shards
params per the policy, and drives the fault-tolerant supervisor.
"""
from __future__ import annotations

import argparse

import jax
import jax.numpy as jnp

from repro.configs import ARCHS
from repro.data import DataConfig, SyntheticLM
from repro.models import lm
from repro.sharding.policies import ShardingPolicy, make_policy
from repro.train import (
    AdamWConfig,
    Supervisor,
    SupervisorConfig,
    TrainStepConfig,
    init_opt_state,
    make_train_step,
)


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True, choices=sorted(ARCHS))
    ap.add_argument("--reduced", action="store_true", help="smoke-scale config")
    ap.add_argument("--steps", type=int, default=50)
    ap.add_argument("--seq", type=int, default=128)
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--microbatches", type=int, default=2)
    ap.add_argument("--ckpt-dir", default="/tmp/repro_ckpt")
    ap.add_argument("--ckpt-every", type=int, default=25)
    ap.add_argument("--resume", action="store_true")
    ap.add_argument("--compression", choices=["none", "int8_ef", "topk_ef"], default="none")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args()

    cfg = ARCHS[args.arch]
    if args.reduced or jax.device_count() == 1:
        cfg = cfg.reduced()
    n_dev = jax.device_count()
    if n_dev > 1:
        from jax.sharding import AxisType

        mesh = jax.make_mesh(
            (n_dev // 2, 2), ("data", "model"), axis_types=(AxisType.Auto,) * 2
        )
        pol = make_policy(mesh)
    else:
        pol = ShardingPolicy()
    print(f"arch={cfg.name} params={cfg.param_count()/1e6:.1f}M devices={n_dev}")

    params = lm.init_params(cfg, jax.random.PRNGKey(args.seed))
    opt = init_opt_state(params)
    data = SyntheticLM(cfg, DataConfig(seq_len=args.seq, global_batch=args.batch, seed=args.seed))
    step = jax.jit(
        make_train_step(
            cfg,
            pol,
            TrainStepConfig(
                n_microbatches=args.microbatches,
                adamw=AdamWConfig(warmup_steps=10, total_steps=args.steps),
                compression=args.compression,
            ),
        )
    )
    sup = Supervisor(
        step,
        params,
        opt,
        lambda s: jax.tree.map(jnp.asarray, data(s)),
        SupervisorConfig(ckpt_dir=args.ckpt_dir, ckpt_every=args.ckpt_every),
    )
    if args.resume:
        try:
            sup.params, sup.opt_state, sup.step = sup.resume_with(params, opt)
            print(f"resumed from step {sup.step}")
        except RuntimeError:
            print("no checkpoint found; starting fresh")
    hist = sup.run(args.steps)
    losses = [h.loss for h in hist]
    print(
        f"steps {hist[0].step}..{hist[-1].step}: loss {losses[0]:.4f} → {losses[-1]:.4f}"
        f"  (restarts={sum(h.restarted for h in hist)},"
        f" stragglers={sum(h.straggler for h in hist)})"
    )


if __name__ == "__main__":
    main()
