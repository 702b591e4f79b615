"""Cell construction: (architecture × input shape × mesh) → a jit-lowerable
computation with fully-specified input shardings and abstract arguments.

A *cell* is the unit of the multi-pod dry-run and the roofline table:

  train_*    → train_step   (fwd + bwd + optimizer update, microbatched)
  prefill_*  → prefill      (full-prompt forward + cache build)
  decode_* / long_* → serve_step (one token against a seq_len KV cache)

Nothing here allocates: parameters, optimizer state, caches and batches are
ShapeDtypeStructs; shardings come from the spec trees declared at module
init, filtered against the target mesh (divisibility-aware)."""
from __future__ import annotations

import dataclasses
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding
from jax.sharding import PartitionSpec as P
from jax import set_mesh

from repro.configs import ArchSpec, ShapeSpec
from repro.distributed.sharding import BATCH_AXES, logical_to_sharding
from repro.models import encdec, lm
from repro.optim import constant_lr, make_optimizer

WHISPER_CROSS_LEN = 1504   # whisper's 1500 encoder frames, padded to /16


def sds(shape, dtype):
    return jax.ShapeDtypeStruct(tuple(shape), jnp.dtype(dtype))


@dataclasses.dataclass
class Cell:
    name: str
    fn: object
    abstract_args: tuple
    in_shardings: tuple
    donate_argnums: tuple
    model_flops: float          # 6·N_active·D (train) / 2·N_active·D (infer)
    meta: dict
    out_shardings: object = None   # None leaves = let XLA choose

    def jitted(self):
        return jax.jit(self.fn, in_shardings=self.in_shardings,
                       out_shardings=self.out_shardings,
                       donate_argnums=self.donate_argnums)

    def lower(self):
        return self.jitted().lower(*self.abstract_args)


def build_optimizer(arch: ArchSpec):
    kw = arch.optimizer_kwargs()
    for k, v in list(kw.items()):
        if isinstance(v, str) and k.endswith("dtype"):
            kw[k] = jnp.dtype(v)
    return make_optimizer(arch.optimizer, **kw)


def _batch_abs_and_spec(cfg, b, s, *, with_labels: bool):
    """LM input batch: tokens or stub embeddings (+ labels)."""
    if getattr(cfg, "frontend", "tokens") == "embeds":
        abs_ = {"embeds": sds((b, s, cfg.d_model), cfg.dtype)}
        spec = {"embeds": P(BATCH_AXES, None, None)}
    else:
        abs_ = {"tokens": sds((b, s), jnp.int32)}
        spec = {"tokens": P(BATCH_AXES, None)}
    if with_labels:
        abs_["labels"] = sds((b, s), jnp.int32)
        spec["labels"] = P(BATCH_AXES, None)
    return abs_, spec


# --------------------------------------------------------------------- #
# LM cells                                                              #
# --------------------------------------------------------------------- #

def _lm_state(arch: ArchSpec, mesh, with_opt: bool):
    cfg = arch.model
    abs_p, specs = lm.abstract_params(cfg)
    p_sh = logical_to_sharding(specs, mesh, abs_p)
    if not with_opt:
        return cfg, abs_p, p_sh, None, None
    opt = build_optimizer(arch)
    abs_o = jax.eval_shape(opt.init, abs_p)
    o_specs = opt.state_specs(specs, abs_p)
    o_sh = logical_to_sharding(o_specs, mesh, abs_o)
    return cfg, abs_p, p_sh, (opt, abs_o), o_sh


def _lm_train_cell(arch: ArchSpec, sh: ShapeSpec, mesh) -> Cell:
    cfg, abs_p, p_sh, (opt, abs_o), o_sh = _lm_state(arch, mesh, True)
    b, s = sh.global_batch, sh.seq_len
    batch_abs, batch_spec = _batch_abs_and_spec(cfg, b, s, with_labels=True)
    b_sh = logical_to_sharding(batch_spec, mesh, batch_abs)
    step_abs = sds((), jnp.int32)
    step_sh = NamedSharding(mesh, P())
    _, specs = lm.abstract_params(cfg)
    fn = lm.make_train_step(cfg, opt, constant_lr(arch.lr),
                            num_micro=arch.micro_for(sh.name), mesh=mesh,
                            param_specs=specs,
                            accum_dtype=jnp.dtype(arch.grad_accum_dtype))
    return Cell(
        name=f"{arch.arch_id}:{sh.name}", fn=fn,
        abstract_args=(abs_p, abs_o, batch_abs, step_abs),
        in_shardings=(p_sh, o_sh, b_sh, step_sh),
        out_shardings=(p_sh, o_sh, None),
        donate_argnums=(0, 1),
        model_flops=6.0 * cfg.num_active_params() * b * s,
        meta={"tokens": b * s, "params": cfg.num_params(),
              "active_params": cfg.num_active_params(),
              "num_micro": arch.micro_for(sh.name)})


def _lm_prefill_cell(arch: ArchSpec, sh: ShapeSpec, mesh) -> Cell:
    cfg, abs_p, p_sh, _, _ = _lm_state(arch, mesh, False)
    b, s = sh.global_batch, sh.seq_len
    batch_abs, batch_spec = _batch_abs_and_spec(cfg, b, s, with_labels=False)
    b_sh = logical_to_sharding(batch_spec, mesh, batch_abs)
    fn = partial(lm.prefill, cfg=cfg, max_len=s, mesh=mesh)

    def wrapped(params, batch):
        return fn(params, batch=batch)

    # output caches must be born sharded (replicated 32k KV would OOM)
    abs_out = jax.eval_shape(wrapped, abs_p, batch_abs)
    c_out_sh = logical_to_sharding(
        lm.generic_cache_specs(abs_out[1]), mesh, abs_out[1])
    return Cell(
        name=f"{arch.arch_id}:{sh.name}", fn=wrapped,
        abstract_args=(abs_p, batch_abs),
        in_shardings=(p_sh, b_sh), donate_argnums=(),
        out_shardings=(None, c_out_sh),
        model_flops=2.0 * cfg.num_active_params() * b * s,
        meta={"tokens": b * s, "params": cfg.num_params(),
              "active_params": cfg.num_active_params()})


def _lm_decode_cell(arch: ArchSpec, sh: ShapeSpec, mesh) -> Cell:
    cfg, abs_p, p_sh, _, _ = _lm_state(arch, mesh, False)
    b, s = sh.global_batch, sh.seq_len
    abs_c = jax.eval_shape(partial(lm.init_caches, cfg, b, s))
    c_specs = lm.cache_specs(cfg, b, s)
    c_sh = logical_to_sharding(c_specs, mesh, abs_c)
    batch_abs, batch_spec = _batch_abs_and_spec(cfg, b, 1, with_labels=False)
    b_sh = logical_to_sharding(batch_spec, mesh, batch_abs)
    pos_abs = sds((b,), jnp.int32)
    pos_sh = logical_to_sharding(P(BATCH_AXES), mesh, pos_abs)
    fn = lm.make_serve_step(cfg, mesh)
    cache_bytes = sum(int(np.prod(l.shape)) * l.dtype.itemsize
                      for l in jax.tree.leaves(abs_c))
    return Cell(
        name=f"{arch.arch_id}:{sh.name}", fn=fn,
        abstract_args=(abs_p, abs_c, batch_abs, pos_abs),
        in_shardings=(p_sh, c_sh, b_sh, pos_sh),
        out_shardings=(None, c_sh),
        donate_argnums=(1,),
        model_flops=2.0 * cfg.num_active_params() * b,
        meta={"tokens": b, "params": cfg.num_params(),
              "active_params": cfg.num_active_params(),
              "kv_cache_bytes": cache_bytes})


# --------------------------------------------------------------------- #
# enc-dec (whisper) cells                                               #
# --------------------------------------------------------------------- #

def _encdec_state(arch: ArchSpec, mesh, with_opt: bool):
    cfg = arch.model
    abs_p, specs = encdec.abstract_params(cfg)
    p_sh = logical_to_sharding(specs, mesh, abs_p)
    if not with_opt:
        return cfg, abs_p, p_sh, None, None
    opt = build_optimizer(arch)
    abs_o = jax.eval_shape(opt.init, abs_p)
    o_sh = logical_to_sharding(opt.state_specs(specs, abs_p), mesh, abs_o)
    return cfg, abs_p, p_sh, (opt, abs_o), o_sh


def _encdec_train_cell(arch: ArchSpec, sh: ShapeSpec, mesh) -> Cell:
    cfg, abs_p, p_sh, (opt, abs_o), o_sh = _encdec_state(arch, mesh, True)
    b, s = sh.global_batch, sh.seq_len
    batch_abs = {"frames": sds((b, s, cfg.d_model), cfg.dtype),
                 "tokens": sds((b, s), jnp.int32),
                 "labels": sds((b, s), jnp.int32)}
    batch_spec = {"frames": P(BATCH_AXES, None, None),
                  "tokens": P(BATCH_AXES, None),
                  "labels": P(BATCH_AXES, None)}
    b_sh = logical_to_sharding(batch_spec, mesh, batch_abs)
    fn = encdec.make_train_step(cfg, opt, constant_lr(arch.lr),
                                num_micro=arch.micro_for(sh.name), mesh=mesh)
    return Cell(
        name=f"{arch.arch_id}:{sh.name}", fn=fn,
        abstract_args=(abs_p, abs_o, batch_abs, sds((), jnp.int32)),
        in_shardings=(p_sh, o_sh, b_sh, NamedSharding(mesh, P())),
        out_shardings=(p_sh, o_sh, None),
        donate_argnums=(0, 1),
        model_flops=6.0 * cfg.num_params() * b * s,
        meta={"tokens": b * s, "params": cfg.num_params(),
              "active_params": cfg.num_params()})


def _encdec_prefill_cell(arch: ArchSpec, sh: ShapeSpec, mesh) -> Cell:
    """Whisper 'prefill' = encode the source + build decode caches."""
    cfg, abs_p, p_sh, _, _ = _encdec_state(arch, mesh, False)
    b, s = sh.global_batch, sh.seq_len
    frames_abs = sds((b, s, cfg.d_model), cfg.dtype)
    f_sh = logical_to_sharding(P(BATCH_AXES, None, None), mesh, frames_abs)

    def fn(params, frames):
        return encdec.prepare_serve_caches(params, cfg, frames,
                                           max_len=min(s, cfg.max_target))

    abs_out = jax.eval_shape(fn, abs_p, frames_abs)
    c_out_sh = logical_to_sharding(lm.generic_cache_specs(abs_out), mesh,
                                   abs_out)
    return Cell(
        name=f"{arch.arch_id}:{sh.name}", fn=fn,
        abstract_args=(abs_p, frames_abs),
        in_shardings=(p_sh, f_sh), donate_argnums=(),
        out_shardings=c_out_sh,
        model_flops=2.0 * cfg.num_params() * b * s,
        meta={"tokens": b * s, "params": cfg.num_params(),
              "active_params": cfg.num_params()})


def _encdec_decode_cell(arch: ArchSpec, sh: ShapeSpec, mesh) -> Cell:
    cfg, abs_p, p_sh, _, _ = _encdec_state(arch, mesh, False)
    b, s = sh.global_batch, sh.seq_len
    a = cfg.attn
    abs_c = {
        "self": jax.eval_shape(partial(encdec.init_self_caches, cfg, b, s)),
        "cross_k": sds((cfg.n_dec_layers, b, WHISPER_CROSS_LEN,
                        a.n_kv_heads, a.d_head), cfg.dtype),
        "cross_v": sds((cfg.n_dec_layers, b, WHISPER_CROSS_LEN,
                        a.n_kv_heads, a.d_head), cfg.dtype),
    }
    c_spec = {
        "self": jax.tree.map(
            lambda l: P(None, BATCH_AXES, "model") if l.ndim == 3
            else P(None, BATCH_AXES, "model", None, None), abs_c["self"]),
        "cross_k": P(None, BATCH_AXES, "model", None, None),
        "cross_v": P(None, BATCH_AXES, "model", None, None),
    }
    c_sh = logical_to_sharding(c_spec, mesh, abs_c)
    batch_abs = {"tokens": sds((b, 1), jnp.int32)}
    b_sh = logical_to_sharding({"tokens": P(BATCH_AXES, None)}, mesh, batch_abs)
    pos_abs = sds((b,), jnp.int32)
    pos_sh = logical_to_sharding(P(BATCH_AXES), mesh, pos_abs)
    fn = encdec.make_serve_step(cfg, mesh)
    return Cell(
        name=f"{arch.arch_id}:{sh.name}", fn=fn,
        abstract_args=(abs_p, abs_c, batch_abs, pos_abs),
        in_shardings=(p_sh, c_sh, b_sh, pos_sh),
        out_shardings=(None, c_sh),
        donate_argnums=(1,),
        model_flops=2.0 * cfg.num_params() * b,
        meta={"tokens": b, "params": cfg.num_params(),
              "active_params": cfg.num_params()})


# --------------------------------------------------------------------- #
# population (the paper's arch) cells                                   #
# --------------------------------------------------------------------- #

def _population_train_cell(arch: ArchSpec, sh: ShapeSpec, mesh) -> Cell:
    from repro.core import parallel_mlp
    pop = arch.model
    abs_p = jax.eval_shape(
        lambda k: parallel_mlp.init_params(k, pop), jax.random.PRNGKey(0))
    # population axis over 'model': zero cross-member collectives (the
    # paper's independence at mesh scale).  ZeRO-style ('model','data')
    # hybrid sharding was tried and REFUTED (§Perf paper-cell iter 4):
    # stateless SGD re-gathers weights 2× per step, costing more than the
    # gradient all-reduce it eliminates (82.7 vs 33.6 MB/dev).
    specs = {"w1": P("model", None), "b1": P("model"),
             "w2": P(None, "model"), "b2": P("model", None)}
    p_sh = logical_to_sharding(specs, mesh, abs_p)
    b = sh.global_batch
    x_abs = sds((b, pop.in_features), jnp.float32)
    y_abs = sds((b,), jnp.int32)
    x_sh = logical_to_sharding(P(BATCH_AXES, None), mesh, x_abs)
    y_sh = logical_to_sharding(P(BATCH_AXES), mesh, y_abs)
    lr = arch.lr

    def fn(params, x, y):
        # act_impl='masked': branchless per-unit activation select.  The
        # sliced path cuts the fused axis at activation-run boundaries that
        # don't align with its 16-way sharding → SPMD rematerialisation
        # (§Perf paper-cell iteration 3; confirmed ~2× on the memory term).
        (loss, per), grads = jax.value_and_grad(
            parallel_mlp.fused_loss, has_aux=True)(
                params, x, y, pop, "classification", act_impl="masked")
        new = jax.tree.map(lambda p, g: p - lr * g, params, grads)
        return new, loss, per

    real_params = sum(h * (pop.in_features + pop.out_features) + h
                      for h in pop.hidden_sizes) \
        + pop.num_members * pop.out_features
    return Cell(
        name=f"{arch.arch_id}:{sh.name}", fn=fn,
        abstract_args=(abs_p, x_abs, y_abs),
        in_shardings=(p_sh, x_sh, y_sh), donate_argnums=(0,),
        model_flops=6.0 * real_params * b,
        meta={"tokens": b, "params": real_params,
              "active_params": real_params,
              "members": pop.num_members,
              "fused_hidden": pop.total_hidden})


# --------------------------------------------------------------------- #
# dispatch                                                              #
# --------------------------------------------------------------------- #

_BUILDERS = {
    ("lm", "train"): _lm_train_cell,
    ("lm", "prefill"): _lm_prefill_cell,
    ("lm", "decode"): _lm_decode_cell,
    ("encdec", "train"): _encdec_train_cell,
    ("encdec", "prefill"): _encdec_prefill_cell,
    ("encdec", "decode"): _encdec_decode_cell,
    ("population", "train"): _population_train_cell,
}


def make_cell(arch: ArchSpec, sh: ShapeSpec, mesh) -> Cell:
    if not arch.runs(sh.name):
        raise ValueError(f"{arch.arch_id} skips {sh.name}: {arch.skip_reason}")
    builder = _BUILDERS[(arch.kind, sh.kind)]
    with set_mesh(mesh):
        return builder(arch, sh, mesh)
