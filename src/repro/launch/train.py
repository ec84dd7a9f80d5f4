"""Production training driver: mesh + sharding rules + data + checkpoint
+ fault tolerance, for any registered arch.

Smoke-scale on this CPU container:
    PYTHONPATH=src python -m repro.launch.train --arch qwen3-0.6b --smoke \
        --steps 30 --batch 8 --seq 64

On a real fleet the same driver runs under a multi-host mesh; the
``--mesh`` flag picks the debug/production topologies. The paper's
co-location layer sits above this driver (launch-level jobs are what
``core.simulator`` schedules).
"""
from __future__ import annotations

import argparse
import signal
import time

import jax
import jax.numpy as jnp

from repro.checkpoint.checkpoint import AsyncCheckpointer, latest_step, \
    restore
from repro.configs import TrainConfig, get_config
from repro.configs.base import ShapeConfig
from repro.data.pipeline import DataConfig, make_batch
from repro.launch import sharding as shd
from repro.models import model as model_lib
from repro.train import optim
from repro.train.step import build_train_step
from repro.utils.compile_cache import enable_compile_cache


def main():
    enable_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--smoke", action="store_true")
    ap.add_argument("--steps", type=int, default=100)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=64)
    ap.add_argument("--lr", type=float, default=1e-3)
    ap.add_argument("--mesh", default="none",
                    help="none | dxm spec like 2x4 (axes data,model)")
    ap.add_argument("--ckpt-dir", default="/tmp/repro_train")
    ap.add_argument("--ckpt-every", type=int, default=50)
    ap.add_argument("--resume", action="store_true")
    ap.add_argument("--ep-moe", action="store_true",
                    help="shard_map expert-parallel MoE path")
    args = ap.parse_args()

    cfg = get_config(args.arch, smoke=args.smoke)
    if args.smoke:
        cfg = cfg.replace(param_dtype="float32", compute_dtype="float32")
    tc = TrainConfig(learning_rate=args.lr, warmup_steps=args.steps // 10,
                     total_steps=args.steps, checkpoint_every=args.ckpt_every,
                     checkpoint_dir=args.ckpt_dir)
    shape = ShapeConfig("train", "train", args.seq, args.batch)
    dc = DataConfig()

    params = model_lib.init(cfg, jax.random.key(0))
    opt = optim.init_opt_state(params, tc)
    step_fn = build_train_step(cfg, tc)

    import contextlib
    ctx = contextlib.nullcontext()
    if args.mesh != "none":
        d, m = (int(x) for x in args.mesh.split("x"))
        mesh = jax.make_mesh((d, m), ("data", "model"))
        abst = jax.tree.map(
            lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype), params)
        ps = shd.param_specs(cfg, abst, mesh, kind="train")
        zs = shd.zero1_opt_specs(ps, abst, mesh)
        from jax.sharding import PartitionSpec as P
        opt_spec = optim.OptState(m=zs, v=zs, count=P())
        dummy = {k: jnp.asarray(v) for k, v in
                 make_batch(cfg, shape, dc, 0).items()}
        bs = shd.batch_specs(dummy, mesh)
        step_fn = jax.jit(
            step_fn,
            in_shardings=(shd.to_named(ps, mesh),
                          shd.to_named(opt_spec, mesh),
                          shd.to_named(bs, mesh)),
            out_shardings=(shd.to_named(ps, mesh),
                           shd.to_named(opt_spec, mesh), None),
            donate_argnums=(0, 1))
        ctx = mesh
        if args.ep_moe and cfg.family == "moe":
            from repro.models.moe_ep import ep_mesh_context
            ctx2 = ep_mesh_context(mesh)
        else:
            ctx2 = contextlib.nullcontext()
    else:
        step_fn = jax.jit(step_fn, donate_argnums=(0, 1))
        ctx2 = contextlib.nullcontext()

    start = 0
    if args.resume and latest_step(args.ckpt_dir) is not None:
        tree, start = restore(
            args.ckpt_dir,
            {"params": params, "m": opt.m, "v": opt.v, "count": opt.count})
        params, opt = tree["params"], optim.OptState(
            m=tree["m"], v=tree["v"], count=tree["count"])
        print(f"resumed from step {start}")

    stop = {"flag": False}
    signal.signal(signal.SIGTERM,
                  lambda *_: stop.__setitem__("flag", True))
    ckpt = AsyncCheckpointer(args.ckpt_dir, keep=tc.keep_checkpoints)
    t0 = time.time()
    with ctx, ctx2:
        for i in range(start, args.steps):
            batch = {k: jnp.asarray(v) for k, v in
                     make_batch(cfg, shape, dc, i).items()}
            params, opt, metrics = step_fn(params, opt, batch)
            if i % 10 == 0 or i == args.steps - 1:
                print(f"step {i:5d} loss={float(metrics['total_loss']):.4f} "
                      f"lr={float(metrics['lr']):.2e}", flush=True)
            if (i + 1) % tc.checkpoint_every == 0 or stop["flag"]:
                ckpt.submit(i + 1, {"params": params, "m": opt.m,
                                    "v": opt.v, "count": opt.count})
            if stop["flag"]:
                print(f"preemption signal: checkpointed at {i + 1}")
                break
    ckpt.close()
    print(f"trained {args.steps - start} steps in {time.time()-t0:.1f}s")


if __name__ == "__main__":
    main()
