"""Sharded training driver (production entry point).

On real hardware this runs under ``jax.distributed`` with one process per
host; on this container it drives the same code over N host devices
(``--devices N`` sets xla_force_host_platform_device_count) so the whole
stack — sharded step, checkpoint/restore, elastic re-mesh — is exercised.

  PYTHONPATH=src python -m repro.launch.train --arch qwen3_1_7b \
      --reduced --devices 8 --steps 20 --dp 4 --tp 2
"""
import argparse
import os
import sys


def _early_env():
    ap = _parser()
    args, _ = ap.parse_known_args()
    if args.devices > 1:
        os.environ["XLA_FLAGS"] = (
            f"--xla_force_host_platform_device_count={args.devices}")
    return args


def _parser():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="qwen3_1_7b")
    ap.add_argument("--reduced", action="store_true")
    ap.add_argument("--devices", type=int, default=1)
    ap.add_argument("--dp", type=int, default=1)
    ap.add_argument("--tp", type=int, default=1)
    ap.add_argument("--steps", type=int, default=20)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=128)
    ap.add_argument("--lr", type=float, default=3e-4)
    ap.add_argument("--optimizer", default="adamw")
    ap.add_argument("--ckpt-dir", default="")
    ap.add_argument("--resume", action="store_true")
    return ap


def main():
    args = _early_env()
    import jax
    import jax.numpy as jnp
    from jax.sharding import NamedSharding, PartitionSpec as P

    from repro.configs import get_config, get_reduced_config
    from repro.data.pipeline import DataPipeline
    from repro.launch import steps as steps_mod
    from repro.optim.optimizers import adamw_init, sgd_init
    from repro.models.model import init_params
    from repro.sharding import logical, rules
    from repro.train.checkpoint import CheckpointManager

    cfg = (get_reduced_config if args.reduced else get_config)(args.arch)
    from repro.launch.mesh import make_mesh
    mesh = make_mesh((args.dp, args.tp), ("data", "model"),
                     jax.devices()[: args.dp * args.tp])
    model = steps_mod.build_model(cfg, mesh)
    pipe = DataPipeline(cfg, global_batch=args.batch, seq_len=args.seq)

    params = init_params(jax.random.PRNGKey(0), cfg)
    init = adamw_init if args.optimizer == "adamw" else sgd_init
    opt_state = init(params)

    lrules = rules.logical_rules(mesh)
    step = steps_mod.make_train_step(cfg, model, lr=args.lr,
                                     optimizer=args.optimizer)
    with mesh, logical.set_rules(mesh, lrules):
        jitted = steps_mod.jit_train_step(
            step, mesh, jax.eval_shape(lambda: params),
            jax.eval_shape(lambda: pipe.batch(0)),
            optimizer=args.optimizer, donate=False)

        pspec = rules.param_pspecs(params, mesh)
        params = jax.tree.map(
            lambda a, s: jax.device_put(a, NamedSharding(mesh, s)),
            params, pspec, is_leaf=lambda x: isinstance(x, P))

        ckpt = CheckpointManager(args.ckpt_dir) if args.ckpt_dir else None
        start = 0
        if ckpt and args.resume and ckpt.latest_step() is not None:
            start, state, _ = ckpt.restore(
                {"params": params, "opt": opt_state})
            params, opt_state = state["params"], state["opt"]
            print(f"resumed from step {start}")

        for i in range(start, args.steps):
            batch = pipe.batch(i)
            params, opt_state, metrics = jitted(params, opt_state, batch)
            if i % 5 == 0 or i == args.steps - 1:
                print(f"step {i:4d}  loss {float(metrics['loss']):.4f}  "
                      f"acc {float(metrics['acc']):.3f}")
            if ckpt and (i + 1) % 10 == 0:
                ckpt.save(i + 1, {"params": params, "opt": opt_state})
        if ckpt:
            ckpt.save(args.steps, {"params": params, "opt": opt_state},
                      block=True)
    print("done")


if __name__ == "__main__":
    main()
