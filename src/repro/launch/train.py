"""Training CLI.

  PYTHONPATH=src python -m repro.launch.train --arch qwen3-0.6b --smoke \
      --steps 100 --ckpt-dir /tmp/run1 [--policy tcec_bf16x6]

On a real TPU fleet this binary runs once per host (jax.distributed
initializes from the TPU environment); the CPU path exercises the identical
trainer, checkpoint, and data code at smoke scale."""
from __future__ import annotations

import argparse

from repro import numerics
from repro.configs import get_config, get_smoke_config, list_archs
from repro.data.pipeline import DataConfig
from repro.optim import adamw
from repro.train.loop import TrainLoopConfig, train


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True, choices=list_archs())
    ap.add_argument("--smoke", action="store_true",
                    help="reduced config (CPU-runnable)")
    ap.add_argument("--policy", default=None,
                    help="GEMM precision policy override")
    ap.add_argument("--steps", type=int, default=100)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=128)
    ap.add_argument("--lr", type=float, default=3e-4)
    ap.add_argument("--ckpt-dir", default="/tmp/repro_ckpt")
    ap.add_argument("--ckpt-every", type=int, default=50)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--mesh-model", type=int, default=0, metavar="N",
                    help="install a (devices/N, N) (data, model) host mesh "
                         "and run the sharded train step (0 = no mesh); "
                         "kernel dispatch then routes through the "
                         "shard_map wrapper (see docs/parallel.md)")
    numerics.add_cli_overrides(ap)
    from repro import obs
    obs.add_cli_flags(ap)
    args = ap.parse_args()
    from repro.launch import compile_cache
    compile_cache.enable()

    with numerics.cli_context(args), obs.cli_session(args):
        _main(args)


def _main(args):
    cfg = (get_smoke_config if args.smoke else get_config)(args.arch)
    if args.policy:
        cfg = cfg.replace(policy=args.policy)
    opt = adamw.OptConfig(lr=args.lr, warmup_steps=max(args.steps // 20, 1),
                          total_steps=args.steps)
    data = DataConfig(seed=args.seed, global_batch=args.batch,
                      seq_len=args.seq)
    loop = TrainLoopConfig(total_steps=args.steps,
                           ckpt_every=args.ckpt_every)
    mesh = None
    if args.mesh_model:
        from repro.launch.mesh import make_host_mesh
        mesh = make_host_mesh(model=args.mesh_model)
        print(f"mesh: {dict(mesh.shape)}", flush=True)

    def log(msg):
        print(msg, flush=True)

    state, hist = train(cfg, opt, data, loop, args.ckpt_dir, log=log,
                        mesh=mesh)
    for h in hist[:: max(len(hist) // 20, 1)]:
        print(f"step {h['step']:5d}  loss {h['loss']:.4f}  "
              f"{h['time_s']*1e3:7.1f} ms")
    print(f"final loss: {hist[-1]['loss']:.4f}")


if __name__ == "__main__":
    main()
