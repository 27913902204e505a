"""Training driver: ``python -m repro.launch.train --arch mamba2-130m ...``

Wires every substrate together: config registry -> model -> sharded state ->
synthetic data pipeline -> jitted train step -> health monitor -> async
atomic checkpoints -> restart loop.  On this CPU box use ``--reduced``
(small config) or the defaults compile forever; on a real pod point
``--mesh`` at the production mesh.
"""
from __future__ import annotations

import argparse
import logging
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.checkpoint import AsyncCheckpointer, ckpt
from repro.configs import get_config
from repro.data import DataConfig, PrefetchIterator, SyntheticLM
from repro.distributed import api as dist_api
from repro.distributed.sharding import make_shardings
from repro.launch.mesh import make_mesh
from repro.models import build_model
from repro.nn.params import init_params
from repro.optim import AdamWConfig, ScheduleConfig
from repro.runtime import StepMonitor
from repro.runtime.compile_cache import enable_compile_cache
from repro.train import TrainConfig, make_train_step

log = logging.getLogger("repro.train")


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="mamba2-130m")
    ap.add_argument("--reduced", action="store_true")
    ap.add_argument("--steps", type=int, default=20)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=128)
    ap.add_argument("--lr", type=float, default=3e-4)
    ap.add_argument("--warmup", type=int, default=10)
    ap.add_argument("--microbatches", type=int, default=1)
    ap.add_argument("--ckpt-dir", default="")
    ap.add_argument("--ckpt-every", type=int, default=50)
    ap.add_argument("--mesh", default="", help="e.g. 2x2:data,model")
    ap.add_argument("--log-every", type=int, default=5)
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)
    logging.basicConfig(level=logging.INFO, format="%(message)s")
    enable_compile_cache()

    cfg = get_config(args.arch, reduced=args.reduced)
    model = build_model(cfg)
    dcfg = DataConfig(vocab_size=cfg.vocab_size, seq_len=args.seq,
                      global_batch=args.batch, seed=args.seed)
    data = SyntheticLM(dcfg)

    train_cfg = TrainConfig(
        optimizer=AdamWConfig(),
        schedule=ScheduleConfig(base_lr=args.lr, warmup_steps=args.warmup,
                                total_steps=max(args.steps, 2)),
        microbatches=args.microbatches)

    mesh = None
    if args.mesh:
        shape_s, axes_s = args.mesh.split(":")
        mesh = make_mesh([int(x) for x in shape_s.split("x")],
                         axes_s.split(","))

    rng = jax.random.PRNGKey(args.seed)
    params = init_params(model.param_specs(), rng, cfg.dtype)
    from repro.optim import adamw
    state = {"params": params, "opt": adamw.init(params, train_cfg.optimizer)}

    step_fn = make_train_step(model, train_cfg, mesh)
    start_step = 0
    ckptr = None
    if args.ckpt_dir:
        ckptr = AsyncCheckpointer(args.ckpt_dir)
        last = ckpt.latest_step(args.ckpt_dir)
        if last is not None:
            state, start_step, extra = ckpt.restore(args.ckpt_dir, state)
            data = SyntheticLM(dcfg, start_step=extra.get("data_step",
                                                          start_step))
            log.info("resumed from step %d", start_step)

    if mesh is not None:
        sh, report = make_shardings(model.param_specs(), mesh)
        log.info("sharding: %s", report.summary())
        state["params"] = jax.tree.map(jax.device_put, state["params"], sh)
        state["opt"]["m"] = jax.tree.map(jax.device_put, state["opt"]["m"], sh)
        state["opt"]["v"] = jax.tree.map(jax.device_put, state["opt"]["v"], sh)
        jitted = jax.jit(step_fn)
    else:
        jitted = jax.jit(step_fn)

    monitor = StepMonitor()
    it = PrefetchIterator(iter(data))

    ctx = mesh if mesh is not None else _nullcontext()
    with ctx:
        for step in range(start_step, args.steps):
            batch = {k: jnp.asarray(v) for k, v in next(it).items()}
            t0 = time.time()
            state, metrics = jitted(state, batch)
            metrics = jax.tree.map(float, jax.device_get(metrics))
            rec = monitor.observe(step, time.time() - t0)
            if step % args.log_every == 0 or step == args.steps - 1:
                log.info("step %4d  loss %.4f  acc %.3f  gnorm %.2f  "
                         "%.2fs%s", step, metrics["loss"],
                         metrics["accuracy"], metrics["grad_norm"],
                         rec.seconds, "  [straggler]" if rec.straggler else "")
            if ckptr and (step + 1) % args.ckpt_every == 0:
                ckptr.save(step + 1, state, {"data_step": data.step})
    if ckptr:
        ckptr.save(args.steps, state, {"data_step": data.step})
        ckptr.wait()
    log.info("done: %s", monitor.summary())
    return state, monitor


class _nullcontext:
    def __enter__(self):
        return self

    def __exit__(self, *a):
        return False


if __name__ == "__main__":
    main()
