"""End-to-end resumable trainer over the overlapped kernel stack.

The user story the reference never ships (it stops at kernels): a training
CLI that wires every framework subsystem together —

- model families: dense Llama (TP; every projection through the overlapped
  AG-GEMM / GEMM-RS kernels) or Mixtral-class MoE (EP AllToAll + grouped
  GEMM, differentiable);
- mesh: 1-D tp or 2-D dp×tp (`--dp`), built from however many devices the
  process sees;
- checkpoint/resume: `runtime.CheckpointManager` — kill the process at any
  step and re-run the same command to continue bit-exactly;
- failure detection: `runtime.Heartbeat` liveness file + per-step stall
  watchdog around the device computation;
- observability: `--profile` wraps the loop in `runtime.group_profile`.

Runs anywhere, TPU or the virtual CPU mesh:

    env JAX_PLATFORMS=cpu \
      XLA_FLAGS=--xla_force_host_platform_device_count=8 \
      python examples/train.py --model moe --dp 2 --steps 20 \
      --ckpt-dir /tmp/run1 --ckpt-every 5
"""

from __future__ import annotations

import argparse
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P


def parse_args():
    p = argparse.ArgumentParser()
    p.add_argument("--model", choices=("llama", "moe"), default="llama")
    p.add_argument("--steps", type=int, default=20)
    p.add_argument("--dp", type=int, default=1, help="data-parallel degree")
    p.add_argument("--batch", type=int, default=4, help="global batch")
    p.add_argument("--seq", type=int, default=64, help="sequence length")
    p.add_argument("--lr", type=float, default=1e-3)
    p.add_argument("--opt", choices=("sgd", "adamw"), default="sgd",
                   help="sgd = the families' fused step; adamw = optax "
                        "(models/training.py), opt state checkpointed too")
    p.add_argument("--seed", type=int, default=0)
    p.add_argument("--ckpt-dir", default=None)
    p.add_argument("--ckpt-every", type=int, default=10)
    p.add_argument("--keep", type=int, default=3)
    p.add_argument("--step-timeout", type=float, default=600.0,
                   help="per-step stall watchdog (seconds)")
    p.add_argument("--heartbeat", default=None,
                   help="liveness file path (default: <ckpt-dir>/heartbeat)")
    p.add_argument("--profile", default=None,
                   help="profile trace directory")
    p.add_argument("--impl", default="auto",
                   choices=("auto", "xla", "pallas"))
    return p.parse_args()


def build(args, mesh, axis, dp_axis):
    """(cfg, params, step_fn, specs) for the chosen family."""
    tp = mesh.shape[axis]
    if args.model == "llama":
        from triton_dist_tpu.models import llama as fam
        cfg = fam.LlamaConfig(vocab=256, dim=32 * tp, n_layers=2,
                              n_heads=tp, n_kv_heads=tp, ffn_dim=128 * tp,
                              max_seq=max(args.seq, 64), dtype=jnp.float32)
    else:
        from triton_dist_tpu.models import moe as fam
        cfg = fam.MoEConfig(vocab=256, dim=32 * tp, n_layers=2,
                            n_heads=tp, n_kv_heads=tp,
                            n_experts=2 * tp, topk=2, expert_ffn_dim=64,
                            max_seq=max(args.seq, 64), block_m=8,
                            dtype=jnp.float32)
    params = fam.place_params(
        fam.init_params(cfg, jax.random.key(args.seed)), cfg, mesh)
    if args.opt == "adamw":
        import optax

        from triton_dist_tpu.models import training
        opt_step, opt_init = training.make_optax_train_step(
            fam, cfg, mesh, optax.adamw(args.lr), axis=axis,
            dp_axis=dp_axis, impl=args.impl)
        state = {"params": params, "opt": opt_init(params)}

        def step_fn(st, tokens, targets):
            p, o, loss = opt_step(st["params"], st["opt"], tokens, targets)
            return {"params": p, "opt": o}, loss
    else:
        sgd_step, _specs = fam.make_train_step(cfg, mesh, axis=axis,
                                               dp_axis=dp_axis,
                                               impl=args.impl, lr=args.lr)
        state = {"params": params}

        def step_fn(st, tokens, targets):
            p, loss = sgd_step(st["params"], tokens, targets)
            return {"params": p}, loss
    return cfg, state, step_fn


def main():
    args = parse_args()
    from triton_dist_tpu.runtime import (
        CheckpointManager, Heartbeat, block_until_ready_with_timeout,
        configure_compile_cache, dist_print, group_profile,
        initialize_distributed)

    configure_compile_cache()

    initialize_distributed()
    n = jax.device_count()
    assert n % args.dp == 0, (n, args.dp)
    tp = n // args.dp
    if args.dp > 1:
        mesh = Mesh(np.array(jax.devices()).reshape(args.dp, tp),
                    ("dp", "tp"))
        dp_axis = "dp"
    else:
        mesh = Mesh(np.array(jax.devices()), ("tp",))
        dp_axis = None
    axis = "tp"
    dist_print(f"mesh {dict(mesh.shape)}  model={args.model}")

    cfg, state, step_fn = build(args, mesh, axis, dp_axis)

    # Deterministic toy data: next-token prediction on a fixed random book.
    key = jax.random.key(args.seed + 1)
    batch_spec = P(axis, dp_axis) if dp_axis else P(axis)
    S, B = args.seq, args.batch
    tokens = jax.device_put(
        jax.random.randint(key, (S, B), 0, cfg.vocab, jnp.int32),
        NamedSharding(mesh, batch_spec))
    targets = jnp.roll(tokens, -1, axis=0)

    start = 0
    mgr = None
    if args.ckpt_dir:
        mgr = CheckpointManager(args.ckpt_dir, max_to_keep=args.keep)
        try:
            resumed = mgr.restore_latest(like=state)
        except Exception:
            # Pre-optimizer checkpoints stored the bare param tree; wrap
            # them into the current {"params": ...} layout on restore.
            if args.opt != "sgd":
                raise
            resumed = mgr.restore_latest(like=state["params"])
            if resumed is not None:
                resumed = (resumed[0], {"params": resumed[1]})
        if resumed is not None:
            start, state = resumed[0] + 1, resumed[1]
            dist_print(f"resumed from step {resumed[0]}")

    hb_path = args.heartbeat or (
        os.path.join(args.ckpt_dir, f"heartbeat.{jax.process_index()}")
        if args.ckpt_dir else None)

    def loop():
        nonlocal state
        saved = start - 1
        for step in range(start, args.steps):
            t0 = time.perf_counter()
            state, loss = step_fn(state, tokens, targets)
            loss = block_until_ready_with_timeout(
                loss, args.step_timeout, name=f"train step {step}")
            dt = time.perf_counter() - t0
            dist_print(f"step {step:4d}  loss {float(loss):.4f}  "
                       f"{dt * 1e3:7.1f} ms")
            if mgr is not None and (step + 1) % args.ckpt_every == 0:
                mgr.save(step, state)
                saved = step
                dist_print(f"checkpointed step {step}")
        if mgr is not None and saved < args.steps - 1:
            mgr.save(args.steps - 1, state)

    import contextlib

    with contextlib.ExitStack() as stack:
        if hb_path:
            stack.enter_context(Heartbeat(hb_path, interval_s=10.0))
        if args.profile:
            stack.enter_context(group_profile("train",
                                              base_dir=args.profile))
        loop()
    dist_print("done")


if __name__ == "__main__":
    main()
