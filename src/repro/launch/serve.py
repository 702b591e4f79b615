"""Serving driver: batched prefill + decode.

``python -m repro.launch.serve --arch qwen3-1.7b --reduced --tokens 64``

Implements the standard two-phase inference flow: prefill the prompt batch
(builds ring-buffer KV caches / SSM states), then step the greedy decode
loop under jit with donated caches.  At full scale the same code lowers
onto the production mesh (decode cells of the dry-run ARE this serve_step).
"""
from __future__ import annotations

import argparse
import time

import jax
import jax.numpy as jnp
import numpy as np
from jax import set_mesh

from repro.configs import get_arch
from repro.distributed.sharding import logical_to_sharding
from repro.launch.mesh import make_host_mesh
from repro.models import encdec, lm


def generate_lm(arch, prompts, max_new: int, mesh, greedy: bool = True,
                temperature: float = 1.0, seed: int = 0):
    """prompts: (B, S) int32 -> (B, S+max_new) tokens + timing dict."""
    cfg = arch.model
    with set_mesh(mesh):
        params, _ = lm.init_params(jax.random.PRNGKey(0), cfg)
        b, s = prompts.shape
        max_len = s + max_new
        t0 = time.time()
        logits, caches = jax.jit(
            lambda p, t: lm.prefill(p, cfg, {"tokens": t}, max_len=max_len,
                                    mesh=mesh))(params, prompts)
        t_prefill = time.time() - t0
        serve_step = jax.jit(lm.make_serve_step(cfg, mesh),
                             donate_argnums=(1,))
        out = [prompts]
        key = jax.random.PRNGKey(seed)
        tok = _pick(logits, greedy, temperature, key)
        t0 = time.time()
        for i in range(max_new):
            out.append(tok)
            if i == max_new - 1:
                break
            pos = jnp.full((b,), s + i, jnp.int32)
            logits, caches = serve_step(params, caches, {"tokens": tok}, pos)
            key, sub = jax.random.split(key)
            tok = _pick(logits, greedy, temperature, sub)
        t_decode = time.time() - t0
        tokens = jnp.concatenate(out, axis=1)
        return tokens, {"prefill_s": t_prefill, "decode_s": t_decode,
                        "tok_per_s": b * max_new / max(t_decode, 1e-9)}


def _pick(logits, greedy, temperature, key):
    if greedy:
        return jnp.argmax(logits[:, -1:], axis=-1).astype(jnp.int32)
    p = logits[:, -1] / temperature
    return jax.random.categorical(key, p, axis=-1)[:, None].astype(jnp.int32)


def generate_encdec(arch, frames, max_new: int, mesh, seed: int = 0):
    cfg = arch.model
    with set_mesh(mesh):
        params, _ = encdec.init_params(jax.random.PRNGKey(0), cfg)
        b = frames.shape[0]
        t0 = time.time()
        caches = jax.jit(
            lambda p, f: encdec.prepare_serve_caches(
                p, cfg, f, max_len=max_new))(params, frames)
        t_prefill = time.time() - t0
        serve_step = jax.jit(encdec.make_serve_step(cfg, mesh),
                             donate_argnums=(1,))
        tok = jnp.zeros((b, 1), jnp.int32)        # BOS
        out = []
        t0 = time.time()
        for i in range(max_new):
            out.append(tok)
            logits, caches = serve_step(params, caches, {"tokens": tok},
                                        jnp.full((b,), i, jnp.int32))
            tok = jnp.argmax(logits, axis=-1).astype(jnp.int32)
        t_decode = time.time() - t0
        return jnp.concatenate(out, axis=1), {
            "prefill_s": t_prefill, "decode_s": t_decode,
            "tok_per_s": b * max_new / max(t_decode, 1e-9)}


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--reduced", action="store_true", default=True)
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--prompt-len", type=int, default=32)
    ap.add_argument("--tokens", type=int, default=32)
    ap.add_argument("--sample", action="store_true")
    args = ap.parse_args(argv)

    arch = get_arch(args.arch, reduced=args.reduced)
    mesh = make_host_mesh()
    rng = np.random.default_rng(0)
    if arch.kind == "encdec":
        frames = rng.normal(0, 1, (args.batch, args.prompt_len,
                                   arch.model.d_model)).astype(np.float32)
        toks, stats = generate_encdec(arch, jnp.asarray(frames), args.tokens,
                                      mesh)
    else:
        prompts = jnp.asarray(rng.integers(
            0, arch.model.vocab, (args.batch, args.prompt_len)), jnp.int32)
        toks, stats = generate_lm(arch, prompts, args.tokens, mesh,
                                  greedy=not args.sample)
    print(f"generated {toks.shape} tokens; {stats}")
    print(np.asarray(toks[:2, -16:]))


if __name__ == "__main__":
    main()
