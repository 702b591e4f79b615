"""End-to-end LM training driver — a ~100M-parameter qwen3-family model for
a few hundred steps on synthetic token data (deliverable (b): the training
kind's end-to-end example).

    PYTHONPATH=src python examples/train_lm.py --steps 300

Uses the SAME code path as the full-scale launcher (repro.launch.train):
jit'd microbatched train step, AdamW, warmup-cosine, async checkpointing,
restart-safe data. On a pod the only difference is the mesh.
"""
import argparse
import time

import jax
import jax.numpy as jnp
from jax import set_mesh

from repro.checkpoint import AsyncCheckpointer
from repro.data import TokenTask
from repro.launch.mesh import make_host_mesh
from repro.models import lm
from repro.models.lm import LayerSpec, LMConfig
from repro.nn.attention import AttnConfig
from repro.nn.ffn import FFNConfig
from repro.optim import adamw, warmup_cosine


def config_100m() -> LMConfig:
    """qwen3-family, ~110M params: 12L d768 12H(kv4) ff2304 qk-norm tied."""
    return LMConfig(
        name="qwen3-100m", vocab=32_000, d_model=768,
        layers=tuple(LayerSpec("attn", "dense", 0) for _ in range(12)),
        attn=AttnConfig(d_model=768, n_heads=12, n_kv_heads=4, d_head=64,
                        qk_norm=True, rope_theta=1e6),
        ffn=FFNConfig(768, 2304, act="silu", gated=True),
        norm="rmsnorm", tie_embeddings=True, param_dtype="float32",
        remat=False)


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--steps", type=int, default=300)
    ap.add_argument("--batch", type=int, default=16)
    ap.add_argument("--seq", type=int, default=256)
    ap.add_argument("--num-micro", type=int, default=2)
    ap.add_argument("--lr", type=float, default=6e-4)
    ap.add_argument("--ckpt-dir", default="/tmp/repro_lm_ckpt")
    args = ap.parse_args()

    cfg = config_100m()
    mesh = make_host_mesh()
    with set_mesh(mesh):
        params, _ = lm.init_params(jax.random.PRNGKey(0), cfg)
        n = sum(x.size for x in jax.tree.leaves(params))
        print(f"model: {cfg.name}  params={n/1e6:.1f}M  "
              f"mesh={dict(mesh.shape)}")
        opt = adamw(weight_decay=0.1)
        opt_state = opt.init(params)
        lr_fn = warmup_cosine(args.lr, warmup_steps=20,
                              total_steps=args.steps)
        step_fn = jax.jit(
            lm.make_train_step(cfg, opt, lr_fn, num_micro=args.num_micro),
            donate_argnums=(0, 1))
        task = TokenTask(vocab=cfg.vocab, seed=0)
        ckpt = AsyncCheckpointer(args.ckpt_dir, every=100)

        tokens_per_step = args.batch * args.seq
        t0 = time.time()
        for s in range(args.steps):
            batch = jax.tree.map(jnp.asarray,
                                 task.batch(s, args.batch, args.seq))
            params, opt_state, m = step_fn(params, opt_state, batch,
                                           jnp.asarray(s, jnp.int32))
            ckpt.maybe_save(s, {"params": params, "opt": opt_state})
            if s % 20 == 0 or s == args.steps - 1:
                dt = time.time() - t0
                tps = tokens_per_step * (s + 1) / dt
                print(f"step {s:4d}  loss {float(m['loss']):.4f}  "
                      f"lr {float(m['lr']):.2e}  "
                      f"grad_norm {float(m['grad_norm']):.2f}  "
                      f"{tps:.0f} tok/s")
        ckpt.wait()
        print(f"done in {time.time()-t0:.1f}s; checkpoints: {ckpt.saved}")


if __name__ == "__main__":
    main()
