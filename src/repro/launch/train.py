"""Cluster training driver: mesh + sharded state + checkpoint/restart +
straggler monitor.  On this container it runs with a host mesh
(XLA_FLAGS device count); on a real fleet the same code path runs per
process with jax.distributed.initialize().

    PYTHONPATH=src python -m repro.launch.train --arch smollm-135m \
        --reduced --steps 30 --mesh 2x2
"""
from __future__ import annotations

import argparse
import time

import jax

from repro.configs import get_config, get_reduced
from repro.configs.base import RunConfig
from repro.data.pipeline import make_loader
from repro.distributed import sharding as SH
from repro.launch.mesh import make_host_mesh
from repro.models import model_init
from repro.train import checkpoint as ckpt
from repro.train.fault import StragglerMonitor, guarded_step
from repro.train.train_step import init_train_state, make_train_step


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="smollm-135m")
    ap.add_argument("--reduced", action="store_true")
    ap.add_argument("--steps", type=int, default=30)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=64)
    ap.add_argument("--mesh", default="auto")
    ap.add_argument("--ckpt-dir", default="/tmp/repro_launch_ckpt")
    ap.add_argument("--ckpt-every", type=int, default=50)
    args = ap.parse_args(argv)

    cfg = get_reduced(args.arch) if args.reduced else get_config(args.arch)
    if args.mesh == "auto":
        mesh = make_host_mesh()
    else:
        d, m = (int(x) for x in args.mesh.split("x"))
        mesh = SH.model_mesh((d, m), ("data", "model"))
    rules = SH.default_rules(False, "train")

    params, specs = model_init(jax.random.PRNGKey(0), cfg)
    psh = SH.tree_sharding(params, specs, rules, mesh)
    params = jax.device_put(params, psh)
    state = init_train_state(params)
    run = RunConfig(model=cfg, remat=True)

    with SH.mesh_context(mesh, rules):
        step = jax.jit(make_train_step(cfg, run), donate_argnums=(0,))
        ds, _ = make_loader(cfg.vocab, args.seq, args.batch)
        start = ckpt.latest_step(args.ckpt_dir) or 0
        if start:
            state, start = ckpt.restore(state, args.ckpt_dir)
            print(f"resumed at {start}")
        mon = StragglerMonitor()
        for i in range(start, args.steps):
            t0 = time.time()
            state, m = guarded_step(step, state, ds.batch_at(i))
            dt = time.time() - t0
            if mon.observe(dt):
                print(f"step {i}: straggler flagged ({dt:.2f}s)")
            if i % 10 == 0 or i == args.steps - 1:
                print(f"step {i:4d} loss {float(m['loss']):.4f} "
                      f"({dt:.2f}s)", flush=True)
            if (i + 1) % args.ckpt_every == 0:
                ckpt.save(state, args.ckpt_dir, step=i + 1)
    print("train driver done")


if __name__ == "__main__":
    main()
