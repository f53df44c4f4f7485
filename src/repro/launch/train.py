"""Training launcher: federated LM rounds with checkpoint/restart.

Runs on whatever devices exist (1 CPU here; the production mesh on TPU).
Fault tolerance: k-replica checkpoints every ``--ckpt-every`` rounds and
restart-from-latest on relaunch (the paper's master-state replication);
elastic scaling: checkpoints hold full logical arrays, so a relaunch on a
different mesh re-shards automatically.  Straggler mitigation: optional
per-round client dropout mask re-weighting the FedAvg average (zero-weight
examples at the loss level).

Compute/communication overlap: microbatch gradient accumulation naturally
pipelines reduce-scatters against the next microbatch's compute; on real
TPU deployments enable async collectives via
  LIBTPU_INIT_ARGS=--xla_tpu_enable_async_collective_fusion=true
  XLA_FLAGS=--xla_tpu_overlap_compute_collective_tc=true (see README).

Usage:
  python -m repro.launch.train --arch tinyllama-1.1b --steps 50 \
      --reduced --ckpt-dir /tmp/ckpt [--resume]

``run(argv)`` is the same path as a function: it takes the command-line
arguments as a list and returns the per-step losses and the set-up
(init + compile) and run times, so other entry points drive the
trainer exactly as the command line does.
"""
from __future__ import annotations

import argparse
import time


def parse_args(argv: list[str] | None = None) -> argparse.Namespace:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="tinyllama-1.1b")
    ap.add_argument("--steps", type=int, default=20)
    ap.add_argument("--seq-len", type=int, default=128)
    ap.add_argument("--global-batch", type=int, default=8)
    ap.add_argument("--grad-accum", type=int, default=1)
    ap.add_argument("--reduced", action="store_true", help="reduced config (CPU-scale)")
    ap.add_argument("--width", type=int, default=0, help="override d_model (reduced)")
    ap.add_argument("--layers", type=int, default=0)
    ap.add_argument("--lr", type=float, default=1e-3)
    ap.add_argument("--ckpt-dir", default=None)
    ap.add_argument("--ckpt-every", type=int, default=10)
    ap.add_argument("--replicas", type=int, default=2)
    ap.add_argument("--resume", action="store_true")
    ap.add_argument("--straggler-rate", type=float, default=0.0,
                    help="simulated per-round client dropout probability")
    ap.add_argument("--non-iid", type=float, default=0.0)
    ap.add_argument("--log-every", type=int, default=5)
    return ap.parse_args(argv)


def run(argv: list[str] | None = None) -> dict:
    """Train as the command line would; returns ``{"cfg", "losses",
    "setup_s", "run_s"}`` (losses of the steps this call ran)."""
    args = parse_args(argv)
    t_setup = time.perf_counter()

    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro import ckpt, configs, data
    from repro.config import RunPlan
    from repro.fl import steps as steps_mod
    from repro.models import encdec, lm

    cfg = configs.get_reduced(args.arch) if args.reduced else configs.get_config(args.arch)
    if args.width:
        cfg = cfg.replace(d_model=args.width, num_heads=max(4, args.width // 32), head_dim=32)
    if args.layers:
        cfg = cfg.replace(num_layers=args.layers)
    cfg = cfg.replace(learning_rate=args.lr)
    plan = RunPlan(grad_accum=args.grad_accum)
    model = encdec if cfg.is_encoder_decoder else lm

    n_dev = jax.device_count()
    print(f"devices={n_dev} arch={cfg.name} layers={cfg.num_layers} d={cfg.d_model}")

    params = model.init_params(jax.random.key(0), cfg)
    state = steps_mod.init_train_state(cfg, params)
    start_step = 0
    if args.resume and args.ckpt_dir and ckpt.latest_step(args.ckpt_dir) is not None:
        state, start_step = ckpt.restore(state, args.ckpt_dir)
        state = jax.device_put(state)  # elastic: re-shard onto current mesh
        print(f"resumed from step {start_step}")

    sc = data.StreamConfig(
        vocab_size=cfg.vocab_size, seq_len=args.seq_len,
        batch_per_shard=args.global_batch, non_iid_alpha=args.non_iid,
    )
    rng = np.random.default_rng(0)

    def make_batch(step):
        batch = data.learnable_lm_batch(sc, shard=0, step=step)
        if args.straggler_rate > 0:
            # deadline-style straggler mitigation: dropped clients' examples
            # get zero weight by masking their labels (paper §III ch.2)
            drop = rng.random(args.global_batch) < args.straggler_rate
            batch["labels"] = np.where(drop[:, None], -1, batch["labels"])
        if cfg.embed_inputs or cfg.is_encoder_decoder:
            emb = data.embeds_batch(sc, cfg.d_model, 0, step)
            b = {"embeds": jnp.asarray(emb), "labels": jnp.asarray(batch["labels"])}
            if cfg.is_encoder_decoder:
                b["tokens"] = jnp.asarray(batch["tokens"])
            return b
        return {k: jnp.asarray(v) for k, v in batch.items()}

    # compile ahead of the loop so set-up (init + compile) and the steps
    # are timed apart
    batches = [make_batch(step) for step in range(start_step, args.steps)]
    train_step = jax.jit(
        steps_mod.build_train_step(cfg, plan), donate_argnums=(0,)
    ).lower(state, batches[0] if batches else make_batch(start_step)).compile()
    setup_s = time.perf_counter() - t_setup
    print(f"set-up (init + compile): {setup_s:.2f} s")

    losses = []
    t0 = time.perf_counter()
    for step, b in zip(range(start_step, args.steps), batches):
        state, metrics = train_step(state, b)
        losses.append(float(metrics["loss"]))
        if step % args.log_every == 0 or step == args.steps - 1:
            print(f"step {step}: loss={losses[-1]:.4f} "
                  f"({(time.perf_counter()-t0)/len(losses)*1e3:.0f} ms/step)")
        if args.ckpt_dir and (step + 1) % args.ckpt_every == 0:
            ckpt.save(state, args.ckpt_dir, step=step + 1, replicas=args.replicas)
    run_s = time.perf_counter() - t0
    if args.ckpt_dir:
        ckpt.save(state, args.ckpt_dir, step=args.steps, replicas=args.replicas)
        print(f"final checkpoint at step {args.steps} ({args.replicas} replicas)")
    print("done")
    return {"cfg": cfg, "losses": losses, "setup_s": setup_s, "run_s": run_s}


def main() -> None:
    from repro.launch import compile_cache

    compile_cache.configure()
    run()


if __name__ == "__main__":
    main()
