"""Training launcher CLI.

GNN (the paper's workload):
    PYTHONPATH=src python -m repro.launch.train --mode gnn --arch gcn \
        --dataset reddit --scale 0.03125 --epochs 30 --isplib on

LM (assigned architectures; reduced config on CPU by default):
    PYTHONPATH=src python -m repro.launch.train --mode lm --arch llama3-8b \
        --smoke --steps 20 --ckpt-dir out/ckpt --resume

The LM path wires the full production substrate: sharded state, resilient
loop (emergency checkpoint + restore), straggler watchdog, async
checkpointing, optional int8 grad compression, optional fault injection
(--inject-fault N crashes step N once to exercise the restart path).
"""
from __future__ import annotations

import argparse
import os
import time

import jax
import numpy as np


def run_gnn(args) -> int:
    from repro.data import make_dataset
    from repro.train import train_gnn

    ds = make_dataset(args.dataset, scale=args.scale, seed=args.seed)
    res = train_gnn(args.arch, ds, hidden=args.hidden, epochs=args.epochs,
                    lr=args.lr, use_isplib=args.isplib == "on",
                    measure_tuning=args.measure_tuning)
    print(f"[gnn] {res.arch} on {res.dataset} (iSpLib={res.use_isplib}, "
          f"plan={res.plan_kind})")
    print(f"  per-epoch {res.epoch_time_s * 1e3:.2f} ms | compile "
          f"{res.compile_time_s:.2f} s | train acc {res.train_acc:.3f} | "
          f"test acc {res.test_acc:.3f}")
    return 0


def run_lm(args) -> int:
    import jax.numpy as jnp
    from repro.configs import get_config, get_smoke_config
    from repro.ckpt import Checkpointer, latest_step
    from repro.data import token_stream
    from repro.launch.mesh import make_local_mesh
    from repro.train import lm as TL
    from repro.train.fault_tolerance import ResilientLoop, StragglerWatchdog

    cfg = get_smoke_config(args.arch) if args.smoke else get_config(args.arch)
    mesh = make_local_mesh(data=args.mesh_data, model=args.mesh_model)
    print(f"[lm] {cfg.name} ({cfg.family}) on mesh {dict(mesh.shape)}")

    if args.grad_sync == "shardmap":
        # explicit data-parallel mode: the step runs under shard_map over
        # 'data' and the gradient reduce is the hand-written collective
        # (int8 wire when --grad-compression int8), not GSPMD's
        assert args.batch % mesh.shape["data"] == 0, \
            (args.batch, dict(mesh.shape))
        step_fn, opt = TL.make_data_parallel_step(
            cfg, mesh, lr=args.lr, accum=args.accum,
            compression=args.grad_compression != "none")
    else:
        step_fn, opt = TL.make_train_step(
            cfg, lr=args.lr, accum=args.accum,
            compression=args.grad_compression != "none")
    with mesh:
        state = TL.make_train_state(
            cfg, jax.random.PRNGKey(args.seed), opt,
            compression=args.grad_compression != "none")
        jit_step = jax.jit(step_fn, donate_argnums=0)

        ckpt = Checkpointer(args.ckpt_dir, keep=3)
        start = 0
        if args.resume and latest_step(args.ckpt_dir) is not None:
            state, start = ckpt.restore(state)
            print(f"  resumed from step {start}")

        fault = {"armed": args.inject_fault >= 0}

        def wrapped_step(st, batch):
            if fault["armed"] and batch["step"] == args.inject_fault:
                fault["armed"] = False
                raise RuntimeError("injected fault (--inject-fault)")
            b = {k: v for k, v in batch.items() if k != "step"}
            return jit_step(st, b)

        def batches():
            for i, (toks, tgts) in enumerate(
                    token_stream(args.batch, args.seq, cfg.vocab,
                                 start_step=start)):
                yield {"tokens": jnp.asarray(toks),
                       "targets": jnp.asarray(tgts), "step": start + i}

        losses = []

        def on_metrics(step, metrics):
            loss = float(metrics["loss"])
            losses.append(loss)
            if step % args.log_every == 0:
                print(f"  step {step:5d} loss {loss:.4f} "
                      f"grad_norm {float(metrics['grad_norm']):.3f}",
                      flush=True)

        loop = ResilientLoop(wrapped_step, ckpt, ckpt_every=args.ckpt_every,
                             watchdog=StragglerWatchdog(),
                             state_shardings=None)
        t0 = time.perf_counter()
        state, end = loop.run(state, batches(), start_step=start,
                              num_steps=args.steps, on_metrics=on_metrics)
        dt = time.perf_counter() - t0
    print(f"  {args.steps} steps in {dt:.1f}s "
          f"({dt / max(args.steps, 1) * 1e3:.1f} ms/step); "
          f"final loss {losses[-1]:.4f}; restarts={loop.restarts}")
    if args.steps >= 20 and args.inject_fault < 0:
        assert losses[-1] < losses[0], "loss did not decrease"
        print("  loss decreased: OK")
    elif len(losses) > 1:
        print(f"  loss {losses[0]:.4f} -> {losses[-1]:.4f}")
    return 0


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--mode", choices=["gnn", "lm"], required=True)
    ap.add_argument("--arch", required=True)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--lr", type=float, default=None)
    # gnn
    ap.add_argument("--dataset", default="reddit")
    ap.add_argument("--scale", type=float, default=1 / 32)
    ap.add_argument("--epochs", type=int, default=30)
    ap.add_argument("--hidden", type=int, default=128)
    ap.add_argument("--isplib", choices=["on", "off"], default="on")
    ap.add_argument("--measure-tuning", action="store_true")
    # lm
    ap.add_argument("--smoke", action="store_true")
    ap.add_argument("--steps", type=int, default=20)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=128)
    ap.add_argument("--accum", type=int, default=1)
    ap.add_argument("--mesh-data", type=int, default=1)
    ap.add_argument("--mesh-model", type=int, default=1)
    ap.add_argument("--ckpt-dir", default="out/ckpt")
    ap.add_argument("--ckpt-every", type=int, default=10)
    ap.add_argument("--log-every", type=int, default=5)
    ap.add_argument("--resume", action="store_true")
    ap.add_argument("--grad-compression", choices=["none", "int8"],
                    default="none")
    ap.add_argument("--grad-sync", choices=["gspmd", "shardmap"],
                    default="gspmd",
                    help="'shardmap' = explicit data-parallel step: "
                         "shard_map over 'data', grads reduced by the "
                         "hand-written collective (int8 wire with "
                         "--grad-compression int8)")
    ap.add_argument("--inject-fault", type=int, default=-1)
    args = ap.parse_args()
    from repro.launch.compile_cache import configure_compile_cache
    configure_compile_cache(os.path.join(os.path.dirname(__file__),
                                         "..", "..", ".."))
    if args.lr is None:
        args.lr = 1e-2 if args.mode == "gnn" else 3e-4
    return run_gnn(args) if args.mode == "gnn" else run_lm(args)


if __name__ == "__main__":
    raise SystemExit(main())
