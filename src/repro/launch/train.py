"""Training launcher.

    PYTHONPATH=src python -m repro.launch.train --arch rwkv6-3b --reduced \\
        --steps 200 --batch 8 --seq 128 --ckpt-dir /tmp/ckpt

On this CPU container the launcher runs reduced configs end to end (the
examples use it to train a ~100M model); on a TPU slice the same entry point
drives the full configs over the production mesh — the mesh/sharding plumbing
is identical, only the device count changes.
"""
from __future__ import annotations

import argparse

import jax

from repro.configs import ARCH_IDS, get_config, get_reduced_config
from repro.data.pipeline import PageTokenDataset, synthetic_data_fn
from repro.dist import meshes
from repro.launch import common
from repro.models import model_zoo
from repro.train.optimizer import OptConfig
from repro.train.train_loop import PreemptionGuard, TrainLoopConfig, run


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", choices=list(ARCH_IDS), required=True)
    ap.add_argument("--reduced", action="store_true",
                    help="reduced same-family config (CPU-runnable)")
    ap.add_argument("--steps", type=int, default=100)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=128)
    ap.add_argument("--lr", type=float, default=3e-4)
    ap.add_argument("--ckpt-dir", default="checkpoints")
    ap.add_argument("--ckpt-every", type=int, default=50)
    ap.add_argument("--remat", default="none", choices=["none", "full", "dots"])
    ap.add_argument("--microbatch", type=int, default=1)
    ap.add_argument("--grad-compression", action="store_true")
    ap.add_argument("--async-ckpt", action="store_true")
    ap.add_argument("--data-path", default="synthetic",
                    choices=["synthetic", "pages"],
                    help="'pages' = DB-page-backed tokens decoded on-device "
                         "by the strider kernel (the paper's data path)")
    # training always ran over the host mesh; --mesh none opts out
    common.add_mesh_flags(ap, default_mesh="host")
    common.add_bench_out_flag(ap)
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)
    common.enable_compile_cache()

    cfg = get_reduced_config(args.arch) if args.reduced else get_config(args.arch)
    print(f"[train] {cfg.name} ({'reduced' if args.reduced else 'full'}): "
          f"{cfg.n_params()/1e6:.1f}M params")

    params, specs = model_zoo.init_params(cfg, jax.random.PRNGKey(args.seed))

    if args.data_path == "pages":
        ds = PageTokenDataset(
            f"{args.ckpt_dir}/tokens.heap", n_seqs=max(args.batch * 8, 64),
            seq_len=args.seq, vocab=cfg.vocab_size, seed=args.seed,
        )
        data_fn = lambda step: ds.batch(step, args.batch)
    else:
        data_fn = synthetic_data_fn(cfg, args.batch, args.seq)

    mesh = common.mesh_from_args(args)
    loop_cfg = TrainLoopConfig(
        total_steps=args.steps,
        ckpt_every=args.ckpt_every,
        ckpt_dir=args.ckpt_dir,
        async_checkpoint=args.async_ckpt,
        grad_compression=args.grad_compression,
    )
    opt_cfg = OptConfig(lr=args.lr, warmup_steps=min(50, args.steps // 4 + 1))
    guard = PreemptionGuard(install=True)

    with meshes.use_mesh(mesh):
        params, opt_state, history = run(
            model_zoo.loss_fn(cfg, remat=args.remat),
            params,
            data_fn,
            loop_cfg,
            opt_cfg,
            preemption=guard,
            hooks=[lambda r: print(
                f"  step {r['step']:5d}  loss {r['loss']:.4f}  "
                f"gnorm {r['grad_norm']:.3f}  {r['s_per_step']*1e3:.0f} ms/step"
            )],
        )
    if history:
        first, last = history[0]["loss"], history[-1]["loss"]
        print(f"[train] loss {first:.4f} -> {last:.4f} "
              f"({'improved' if last < first else 'NOT improved'})")
    common.write_bench_out(args, {
        "arch": cfg.name,
        "steps": len(history),
        "loss_first": history[0]["loss"] if history else None,
        "loss_last": history[-1]["loss"] if history else None,
        "mean_s_per_step": (sum(r["s_per_step"] for r in history)
                            / len(history)) if history else None,
    })
    return history


if __name__ == "__main__":
    main()
