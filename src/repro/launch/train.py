"""Cluster training entry point.

On a real TPU cluster every host runs::

    python -m repro.launch.train --arch granite-8b --batch 256 --seq 4096

jax.distributed is initialized from the standard TPU environment; the
mesh spans all global devices (multi-pod when the slice topology provides
it); each host's data shard comes from its process index.  On CPU this
runs single-process (useful with --smoke).
"""

from __future__ import annotations

import argparse
import dataclasses
import os
import tempfile

import jax

from repro.launch.compile_cache import configure_compile_cache


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--batch", type=int, default=256)
    ap.add_argument("--seq", type=int, default=4096)
    ap.add_argument("--steps", type=int, default=1000)
    ap.add_argument("--accum", type=int, default=1)
    ap.add_argument("--lr", type=float, default=3e-4)
    ap.add_argument("--ckpt-dir", default=os.path.join(
        tempfile.gettempdir(), "branchx-ckpt"))
    ap.add_argument("--ckpt-every", type=int, default=100)
    ap.add_argument("--compress-grads", default=None,
                    choices=[None, "int8", "topk"])
    ap.add_argument("--smoke", action="store_true",
                    help="reduced config, CPU-sized")
    ap.add_argument("--distributed", action="store_true",
                    help="initialize jax.distributed (TPU pods)")
    args = ap.parse_args(argv)
    configure_compile_cache()

    if args.distributed:
        jax.distributed.initialize()

    from repro.checkpoint import CheckpointManager
    from repro.configs import get_config, reduced
    from repro.data import SyntheticLMPipeline
    from repro.models.model import Model
    from repro.optim import adamw, cosine_warmup
    from repro.runtime.elastic import plan_mesh
    from repro.runtime.fault import FaultTolerantTrainer
    from repro.runtime.train_loop import build_train_step, init_train_state
    from repro.distributed.sharding import param_shardings, shard_params

    cfg = get_config(args.arch)
    if args.smoke:
        cfg = dataclasses.replace(reduced(cfg), dtype="float32")
        args.batch, args.seq, args.steps = 2, 32, 10

    n_dev = len(jax.devices())
    plan = plan_mesh(jax.devices()) if n_dev > 1 else None
    model = Model(cfg, plan=plan) if plan else Model(
        cfg, attn_chunk=min(256, args.seq), loss_chunk=min(128, args.seq))

    opt = adamw(cosine_warmup(args.lr, max(args.steps // 20, 1),
                              args.steps))
    step = jax.jit(
        build_train_step(model, opt, accum_steps=args.accum,
                         compress=args.compress_grads),
        donate_argnums=(0,),
    )
    state = init_train_state(model, opt, jax.random.PRNGKey(0),
                             compress=args.compress_grads)
    if plan:
        state = state._replace(
            params=shard_params(cfg, plan, state.params),
            opt_state=jax.tree_util.tree_map(
                jax.device_put, state.opt_state,
                param_shardings(cfg, plan, state.opt_state)))

    shard = jax.process_index()
    data = SyntheticLMPipeline(
        cfg, batch=args.batch // max(jax.process_count(), 1),
        seq=args.seq, seed=7, shard=shard,
        num_shards=max(jax.process_count(), 1))

    trainer = FaultTolerantTrainer(
        step_fn=step, state=state, data=data,
        ckpt=CheckpointManager(args.ckpt_dir),
        ckpt_every=args.ckpt_every)
    trainer.run(args.steps)
    m = trainer.metrics_log[-1]
    print(f"done: step {trainer.steps_done} loss {m['loss']:.4f} "
          f"rollbacks {trainer.rollbacks}")
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
