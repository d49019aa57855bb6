"""Training launcher: ``python -m repro.launch.train --arch <id> [...]``.

Runs REAL steps (allocates params, iterates data) — on this CPU container
use ``--smoke`` (reduced same-family config) or a custom ``--d-model`` etc.;
on a pod the same entry point takes the full config and the production mesh.

Example (CPU):
    PYTHONPATH=src python -m repro.launch.train --arch qwen2-1.5b --smoke \
        --steps 20 --batch 8 --seq 128
"""
from __future__ import annotations

import argparse
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.checkpoint import latest_step, restore_checkpoint, save_checkpoint
from repro.configs import ARCH_IDS, get_config, get_smoke
from repro.data import BatchIterator, SyntheticLMDataset
from repro.launch.compile_cache import enable_compile_cache
from repro.launch.mesh import make_host_mesh
from repro.optim.optimizers import adamw
from repro.train.step import init_train_state, make_train_step


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--arch", required=True, choices=ARCH_IDS)
    ap.add_argument("--smoke", action="store_true",
                    help="reduced same-family config (CPU-friendly)")
    ap.add_argument("--steps", type=int, default=50)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=128)
    ap.add_argument("--lr", type=float, default=3e-4)
    ap.add_argument("--ckpt-dir", default=None)
    ap.add_argument("--ckpt-every", type=int, default=100)
    ap.add_argument("--log-every", type=int, default=10)
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args()
    enable_compile_cache()

    cfg = get_smoke(args.arch) if args.smoke else get_config(args.arch)
    print(f"arch={cfg.name} layers={cfg.num_layers} d_model={cfg.d_model} "
          f"params≈{param_count_estimate(cfg)/1e6:.1f}M")

    optimizer = adamw(lr=args.lr, total_steps=args.steps)
    state, axes = init_train_state(jax.random.PRNGKey(args.seed), cfg, optimizer)
    n_params = sum(int(np.prod(p.shape)) for p in jax.tree.leaves(state.params))
    print(f"params={n_params/1e6:.2f}M")

    start = 0
    if args.ckpt_dir and latest_step(args.ckpt_dir) is not None:
        state, start = restore_checkpoint(args.ckpt_dir, state)
        print(f"restored step {start}")

    step_fn = make_train_step(cfg, optimizer)
    ds = SyntheticLMDataset(vocab_size=cfg.vocab_size, seq_len=args.seq,
                            batch_size=args.batch, seed=args.seed)

    def with_extras(step: int) -> dict:
        b = ds.batch(step)
        if cfg.vision_tokens:
            b["vision_embeds"] = np.zeros(
                (args.batch, cfg.vision_tokens, cfg.d_model), np.float32)
        if cfg.encoder_layers:
            b["encoder_frames"] = np.random.default_rng(step).normal(
                size=(args.batch, cfg.encoder_seq, cfg.d_model)).astype(np.float32)
        return b

    it = BatchIterator(with_extras, start_step=start)
    t0 = time.time()
    losses = []
    for step in range(start, args.steps):
        batch = next(it)
        state, metrics = step_fn(state, batch)
        losses.append(float(metrics["loss"]))
        if step % args.log_every == 0 or step == args.steps - 1:
            dt = time.time() - t0
            toks = args.batch * args.seq * (step - start + 1)
            print(f"step {step:5d} loss {losses[-1]:.4f} "
                  f"grad_norm {float(metrics['grad_norm']):.3f} "
                  f"tok/s {toks/dt:,.0f}")
        if args.ckpt_dir and (step + 1) % args.ckpt_every == 0:
            save_checkpoint(args.ckpt_dir, step + 1, state)
    if args.ckpt_dir:
        save_checkpoint(args.ckpt_dir, args.steps, state)
    first, last = losses[0], losses[-1]
    print(f"done: loss {first:.4f} -> {last:.4f} "
          f"({'improved' if last < first else 'NOT improved'})")


def param_count_estimate(cfg) -> float:
    """Rough non-embedding parameter count for the banner."""
    d, L, f = cfg.d_model, cfg.num_layers, cfg.d_ff
    per = 4 * d * d + (3 if cfg.mlp_kind == "swiglu" else 2) * d * f * max(cfg.num_experts, 1)
    return L * per + cfg.vocab_size * d


if __name__ == "__main__":
    main()
