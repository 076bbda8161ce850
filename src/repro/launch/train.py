"""Training driver: sharded pjit train loop with checkpoint/restart,
straggler monitoring, optional SPARQ gradient compression.

Local (CPU) runs use reduced configs:
  PYTHONPATH=src python -m repro.launch.train --arch tinyllama-1.1b \
      --reduced --steps 50 --batch 8 --seq 128 --checkpoint-dir /tmp/ckpt

On a real cluster the same entry point runs the full config on the
production mesh (--mesh production [--multi-pod]).
"""
from __future__ import annotations

import argparse
import time
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.checkpoint import manager as ckpt
from repro.configs.base import get_config, get_reduced_config
from repro.data.pipeline import Batcher, DataConfig
from repro.distributed import sharding as shd
from repro.distributed.collectives import GradCompressor
from repro.distributed.fault import ElasticCoordinator
from repro.launch.mesh import make_host_mesh, make_production_mesh
from repro.models.model import Model
from repro.optim.adamw import AdamW, cosine_schedule


def build_train_step(model: Model, opt: AdamW,
                     compressor: GradCompressor | None = None,
                     accum: int | None = None):
    """Gradient-accumulating train step. `accum` microbatches (default from
    cfg.train_microbatches) bound activation memory: each microbatch's
    activations are freed before the next starts; only the f32 grad
    accumulator (params-sized, params-sharded) persists."""
    accum = accum or model.cfg.train_microbatches

    def loss_fn(p, mb):
        loss, metrics = model.loss(p, mb)
        return loss, metrics

    def train_step(params, opt_state, comp_state, batch):
        if accum > 1:
            mbs = jax.tree.map(
                lambda x: x.reshape(accum, x.shape[0] // accum,
                                    *x.shape[1:]), batch)

            def micro(carry, mb):
                g_acc, l_acc = carry
                (loss, _), g = jax.value_and_grad(
                    loss_fn, has_aux=True)(params, mb)
                g_acc = jax.tree.map(
                    lambda a, b: a + b.astype(jnp.float32), g_acc, g)
                return (g_acc, l_acc + loss), None

            g0 = jax.tree.map(lambda p: jnp.zeros(p.shape, jnp.float32),
                              params)
            (grads, loss), _ = jax.lax.scan(
                micro, (g0, jnp.float32(0)), mbs)
            grads = jax.tree.map(lambda g: g / accum, grads)
            loss = loss / accum
            metrics = {"lm_loss": loss}
        else:
            (loss, metrics), grads = jax.value_and_grad(
                loss_fn, has_aux=True)(params, batch)
        if compressor is not None:
            grads, comp_state = compressor.compress(grads, comp_state)
        new_params, new_state, om = opt.update(grads, opt_state, params)
        return new_params, new_state, comp_state, {
            "loss": loss, **{k: v for k, v in metrics.items()}, **om}
    return train_step


def shard_tree(tree, mesh, specs):
    return jax.tree.map(
        lambda x, s: jax.device_put(x, NamedSharding(mesh, s)), tree, specs,
        is_leaf=lambda x: not isinstance(x, (dict, list, tuple)))


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="tinyllama-1.1b")
    ap.add_argument("--reduced", action="store_true")
    ap.add_argument("--steps", type=int, default=100)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=128)
    ap.add_argument("--lr", type=float, default=3e-4)
    ap.add_argument("--lr-total", type=int, default=None,
                    help="schedule horizon (default: --steps); set it\n                    explicitly when a run will be resumed/extended")
    ap.add_argument("--mesh", choices=["host", "production"], default="host")
    ap.add_argument("--multi-pod", action="store_true")
    ap.add_argument("--model-parallel", type=int, default=1)
    ap.add_argument("--checkpoint-dir", default=None)
    ap.add_argument("--checkpoint-every", type=int, default=50)
    ap.add_argument("--restore", action="store_true")
    ap.add_argument("--compress-grads", action="store_true")
    ap.add_argument("--log-every", type=int, default=10)
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)

    cfg = get_reduced_config(args.arch) if args.reduced \
        else get_config(args.arch)
    model = Model(cfg)
    total = args.lr_total or args.steps
    opt = AdamW(lr=cosine_schedule(args.lr, max(total // 20, 1),
                                   total))
    compressor = GradCompressor() if args.compress_grads else None

    mesh = make_production_mesh(multi_pod=args.multi_pod) \
        if args.mesh == "production" else \
        make_host_mesh(args.model_parallel)
    shd.set_activation_spec(shd.activation_spec(mesh, sp=False), mesh=mesh)
    try:
        data = Batcher(DataConfig(
            vocab_size=cfg.vocab_size, seq_len=args.seq,
            global_batch=args.batch, seed=args.seed,
            frontend=cfg.frontend, frontend_len=cfg.frontend_len,
            d_model=cfg.d_model))

        with mesh:
            params = model.init_params(jax.random.PRNGKey(args.seed))
            p_specs = shd.param_pspecs(params, mesh)
            params = shard_tree(params, mesh, p_specs)
            opt_state = opt.init(params)
            comp_state = compressor.init(params) if compressor else None

            start_step = 0
            if args.checkpoint_dir and args.restore:
                step = ckpt.latest_step(args.checkpoint_dir)
                if step is not None:
                    shardings = jax.tree.map(
                        lambda s: NamedSharding(mesh, s), p_specs,
                        is_leaf=lambda x: isinstance(x, P))
                    state = ckpt.restore(
                        args.checkpoint_dir, step,
                        {"params": params, "m": opt_state.m, "v": opt_state.v},
                        {"params": shardings, "m": shardings, "v": shardings})
                    params = state["params"]
                    opt_state = opt_state._replace(
                        m=state["m"], v=state["v"],
                        count=jnp.asarray(step, jnp.int32))
                    start_step = step
                    print(f"restored step {step} from {args.checkpoint_dir}")

            step_fn = jax.jit(build_train_step(model, opt, compressor),
                              donate_argnums=(0, 1, 2))
            coord = ElasticCoordinator(n_workers=jax.process_count())

            losses = []
            for step in range(start_step, args.steps):
                t0 = time.perf_counter()
                batch = data.global_batch(step)
                params, opt_state, comp_state, metrics = step_fn(
                    params, opt_state, comp_state, batch)
                dt = time.perf_counter() - t0
                coord.step_report(jax.process_index(), step, dt)
                losses.append(float(metrics["loss"]))
                if step % args.log_every == 0 or step == args.steps - 1:
                    print(f"step {step} loss {float(metrics['loss']):.4f} "
                          f"gnorm {float(metrics['grad_norm']):.3f} "
                          f"({dt*1000:.0f} ms)", flush=True)
                if args.checkpoint_dir and \
                        (step + 1) % args.checkpoint_every == 0:
                    ckpt.save(args.checkpoint_dir, step + 1,
                              {"params": params, "m": opt_state.m,
                               "v": opt_state.v})
            if args.checkpoint_dir:
                ckpt.save(args.checkpoint_dir, args.steps,
                          {"params": params, "m": opt_state.m, "v": opt_state.v})
    finally:
        # module-global state: reset it on failure too, or every later
        # caller in this process inherits a dead mesh
        shd.set_activation_spec(None, None)
    return losses


if __name__ == "__main__":
    main()
