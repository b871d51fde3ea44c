"""Production training launcher: pjit'ed train step under a device mesh.

On real hardware, jax.distributed.initialize() + the production mesh make
this the multi-pod entry point; on this container it runs on whatever
devices exist (default 1).  Checkpoint/restart + straggler logging come
from repro.train.trainer semantics, re-implemented here against the
sharded step.

  PYTHONPATH=src python -m repro.launch.train --arch yi-9b --smoke \
      --steps 20 --batch 8 --seq 128
"""
from __future__ import annotations

import argparse
import time
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P

from repro.checkpoint import store
from repro.configs import get_config, smoke_config
from repro.launch.compile_cache import setup_compile_cache
from repro.data.pipeline import DataConfig, SyntheticLM
from repro.models import model as M
from repro.optim import adamw
from repro.parallel import sharding as SH
from repro.parallel.context import sharding_ctx
from repro.train.trainer import train_step


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="yi-9b")
    ap.add_argument("--smoke", action="store_true",
                    help="reduced config (CPU-friendly)")
    ap.add_argument("--steps", type=int, default=20)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=128)
    ap.add_argument("--lr", type=float, default=1e-3)
    ap.add_argument("--ckpt", default="")
    ap.add_argument("--mesh", default="auto",
                    help="'auto' = all devices on one 'data' axis; "
                         "'DxM' = explicit (data, model) grid")
    args = ap.parse_args()
    setup_compile_cache()

    cfg = (smoke_config(args.arch) if args.smoke else
           get_config(args.arch).replace(dtype="bfloat16"))
    n_dev = jax.device_count()
    if args.mesh == "auto":
        mesh = SH.make_mesh((n_dev, 1), ("data", "model"))
    else:
        d, m = map(int, args.mesh.split("x"))
        mesh = SH.make_mesh((d, m), ("data", "model"))
    print(f"arch={cfg.name} params={cfg.param_count()/1e6:.1f}M "
          f"mesh={dict(mesh.shape)}")

    ocfg = adamw.AdamWConfig(lr_peak=args.lr, warmup_steps=max(args.steps // 10, 1),
                             total_steps=args.steps)
    params = M.init(jax.random.PRNGKey(0), cfg)
    opt_state = adamw.init_state(params, ocfg)
    start = 0
    if args.ckpt and store.latest_step(args.ckpt) is not None:
        (params, opt_state), start = store.restore(args.ckpt, (params, opt_state))
        print(f"restored step {start} from {args.ckpt}")

    p_ps = SH.param_pspecs(params, mesh)
    p_sh = SH.named(mesh, p_ps)
    o_sh = SH.named(mesh, {"step": P(), "m": p_ps, "v": p_ps}
                    if "master" not in opt_state
                    else {"step": P(), "m": p_ps, "v": p_ps, "master": p_ps})
    data = SyntheticLM(DataConfig(seed=0, batch_size=args.batch,
                                  seq_len=args.seq), cfg)
    b0 = {k: jnp.asarray(v) for k, v in data.batch(0).items()}
    b_sh = SH.named(mesh, SH.batch_pspecs(b0, mesh))

    with mesh, sharding_ctx(mesh, SH.batch_axes(mesh)):
        step_fn = jax.jit(partial(train_step, cfg=cfg, opt_cfg=ocfg),
                          in_shardings=(p_sh, o_sh, b_sh),
                          donate_argnums=(0, 1))
        params = jax.device_put(params, p_sh)
        opt_state = jax.device_put(opt_state, o_sh)
        for step in range(start, args.steps):
            batch = jax.device_put(
                {k: jnp.asarray(v) for k, v in data.batch(step).items()}, b_sh)
            t0 = time.monotonic()
            params, opt_state, metrics = step_fn(params, opt_state, batch)
            loss = float(metrics["loss"])
            print(f"step {step:5d} loss {loss:.4f} "
                  f"({(time.monotonic()-t0)*1e3:.0f} ms)")
            if args.ckpt and (step + 1) % 50 == 0:
                store.save(args.ckpt, step + 1,
                           (jax.device_get(params), jax.device_get(opt_state)))
    if args.ckpt:
        store.save(args.ckpt, args.steps,
                   (jax.device_get(params), jax.device_get(opt_state)))


if __name__ == "__main__":
    main()
