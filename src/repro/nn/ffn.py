"""Feed-forward layers: gated/non-gated dense FFNs and Mixture-of-Experts.

The MoE layer is where the paper's technique becomes a first-class framework
feature (DESIGN.md §4): expert computation is the row-segment dual of M3 —
tokens grouped by expert, each group multiplying its own weights, results
scattered back to token order with gradients flowing only through each
token's own experts.  Two interchangeable implementations:

  * ``moe_apply_dense``      — capacity-padded scatter/gather formulation,
    auto-shardable by GSPMD, runs anywhere (smoke tests, single host).
  * ``moe_apply_shard_map``  — explicit SP+EP formulation: tokens
    sequence-sharded over the 'model' axis for routing, expert buffers
    exchanged with ``lax.all_to_all``, experts sharded over 'model'
    (expert parallelism).  This is the production path; the all-to-all pair
    is visible in the dry-run HLO for the roofline's collective term.

On TPU runtime the per-expert matmuls can route through the Pallas grouped
GEMM (kernels/moe_gemm.py); under XLA:CPU and in the dry-run they lower to
batched einsums (same math — asserted in tests).
"""
from __future__ import annotations

import dataclasses
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P

from repro.nn.common import FFN_ACTS, dense_init


# --------------------------------------------------------------------- #
# dense FFN                                                             #
# --------------------------------------------------------------------- #

@dataclasses.dataclass(frozen=True)
class FFNConfig:
    d_model: int
    d_ff: int
    act: str = "silu"       # silu|gelu|relu2|relu
    gated: bool = True      # SwiGLU/GeGLU when True
    bias: bool = False


def ffn_init(key, cfg: FFNConfig, dtype):
    k1, k2, k3 = jax.random.split(key, 3)
    params, specs = {}, {}
    p, s = dense_init(k1, cfg.d_model, cfg.d_ff, dtype, P("data", "model"),
                      bias=cfg.bias)
    params["w_up"], specs["w_up"] = p, s
    if cfg.gated:
        p, s = dense_init(k2, cfg.d_model, cfg.d_ff, dtype, P("data", "model"),
                          bias=cfg.bias)
        params["w_gate"], specs["w_gate"] = p, s
    p, s = dense_init(k3, cfg.d_ff, cfg.d_model, dtype, P("model", "data"),
                      bias=cfg.bias, stddev=cfg.d_ff ** -0.5)
    params["w_down"], specs["w_down"] = p, s
    return params, specs


def ffn_apply(p, cfg: FFNConfig, x):
    act = FFN_ACTS[cfg.act]
    up = x @ p["w_up"]["w"]
    up = _tp_inner(up)
    if cfg.bias:
        up = up + p["w_up"]["b"]
    if cfg.gated:
        gate = x @ p["w_gate"]["w"]
        gate = _tp_inner(gate)
        if cfg.bias:
            gate = gate + p["w_gate"]["b"]
        h = act(gate) * up
    else:
        h = act(up)
    y = h @ p["w_down"]["w"]
    if cfg.bias:
        y = y + p["w_down"]["b"]
    return y


def _tp_inner(h):
    """Pin the FFN inner dim to the 'model' axis (Megatron TP).

    Without this, the SP residual (S on 'model') propagates into the layer
    and the inner activations stay model-REPLICATED on the F dim — the
    backward then builds FULL (D,F) weight grads and all-reduces them at
    full size (nemotron: 5.06 GiB dW buffers + 12.9 GiB/layer all-reduces
    in the baseline dry-run).  Constraining h makes dW born (D, F/tp):
    §Perf hillclimb iteration 1.  Width-gated (TP_INNER_MIN_COLS): for
    narrow layers the AG/RS transitions cost more than the dW savings."""
    from repro.distributed.sharding import (BATCH_AXES, TP_INNER_MIN_COLS,
                                            constrain)
    if h.ndim == 3 and h.shape[-1] >= TP_INNER_MIN_COLS:
        return constrain(h, P(BATCH_AXES, None, "model"))
    return h


# --------------------------------------------------------------------- #
# MoE                                                                   #
# --------------------------------------------------------------------- #

@dataclasses.dataclass(frozen=True)
class MoEConfig:
    d_model: int
    d_expert: int
    num_experts: int
    top_k: int
    num_shared: int = 0          # always-on shared experts (DeepSeek-MoE)
    renorm_topk: bool = True     # Mixtral renormalises top-k gates
    capacity_factor: float = 1.25
    act: str = "silu"
    aux_loss_coef: float = 0.01
    first_k_dense: int = 0       # leading layers use a dense FFN instead
    dense_ff: int = 0            # width of those dense layers
    sharding: str = "ep"         # 'ep': experts over 'model' (all-to-all);
                                 # 'tp': expert F-dim over 'model' (E < mesh,
                                 #       e.g. mixtral's 8 experts on 16 chips)


def moe_init(key, cfg: MoEConfig, dtype):
    kr, ke, ks = jax.random.split(key, 3)
    d, f, e = cfg.d_model, cfg.d_expert, cfg.num_experts
    params = {"router": jax.random.normal(kr, (d, e), jnp.float32) * d ** -0.5}
    specs = {"router": P(None, None)}
    kg, ku, kd = jax.random.split(ke, 3)
    # experts stacked on a leading E axis -> EP over 'model'
    std = d ** -0.5
    params["experts"] = {
        "w_gate": jax.random.normal(kg, (e, d, f), dtype) * std,
        "w_up": jax.random.normal(ku, (e, d, f), dtype) * std,
        "w_down": jax.random.normal(kd, (e, f, d), dtype) * f ** -0.5,
    }
    if cfg.sharding == "ep":
        specs["experts"] = {
            "w_gate": P("model", "data", None),
            "w_up": P("model", "data", None),
            "w_down": P("model", None, "data"),
        }
    else:  # 'tp': shard the expert inner dim; experts replicated over EP
        specs["experts"] = {
            "w_gate": P(None, "data", "model"),
            "w_up": P(None, "data", "model"),
            "w_down": P(None, "model", "data"),
        }
    if cfg.num_shared:
        shared_cfg = FFNConfig(d, cfg.d_expert * cfg.num_shared, act=cfg.act)
        p, s = ffn_init(ks, shared_cfg, dtype)
        params["shared"], specs["shared"] = p, s
    return params, specs


def _route(router_w, cfg: MoEConfig, xf):
    """xf (T, D) -> gates (T, k), expert ids (T, k), aux load-balance loss."""
    logits = (xf.astype(jnp.float32) @ router_w)                 # (T, E)
    probs = jax.nn.softmax(logits, axis=-1)
    gate_vals, eidx = jax.lax.top_k(probs, cfg.top_k)            # (T, k)
    if cfg.renorm_topk:
        gate_vals = gate_vals / jnp.maximum(gate_vals.sum(-1, keepdims=True), 1e-9)
    # Switch-style load-balancing aux loss
    me = probs.mean(0)                                           # (E,)
    ce = jnp.zeros((cfg.num_experts,)).at[eidx.reshape(-1)].add(
        1.0 / eidx.size)
    aux = cfg.num_experts * jnp.sum(me * ce) * cfg.aux_loss_coef
    return gate_vals.astype(xf.dtype), eidx, aux


def _expert_ffn(experts, cfg: MoEConfig, buf):
    """buf (E, C, D) -> (E, C, D), SwiGLU per expert (batched einsum)."""
    act = FFN_ACTS[cfg.act]
    h = act(jnp.einsum("ecd,edf->ecf", buf, experts["w_gate"])) * \
        jnp.einsum("ecd,edf->ecf", buf, experts["w_up"])
    return jnp.einsum("ecf,efd->ecd", h, experts["w_down"])


def _dispatch_combine(p, cfg: MoEConfig, xf, capacity: int):
    """Capacity-padded dispatch -> expert FFN -> combine.  xf (T, D)."""
    t, d = xf.shape
    gates, eidx, aux = _route(p["router"], cfg, xf)
    flat_e = eidx.reshape(-1)                                     # (T*k,)
    # position of each (token, expert-slot) within its expert's buffer
    onehot = jax.nn.one_hot(flat_e, cfg.num_experts, dtype=jnp.int32)
    pos = jnp.cumsum(onehot, axis=0) * onehot - 1                 # (T*k, E)
    pos = pos.max(axis=-1)                                        # (T*k,)
    dst = jnp.where(pos < capacity, flat_e * capacity + pos,
                    cfg.num_experts * capacity)                   # drop slot
    src = jnp.repeat(jnp.arange(t), cfg.top_k)
    buf = jnp.zeros((cfg.num_experts * capacity + 1, d), xf.dtype)
    buf = buf.at[dst].set(xf[src], mode="drop")
    out = _expert_ffn(p["experts"], cfg,
                      buf[:-1].reshape(cfg.num_experts, capacity, d))
    out = out.reshape(-1, d)
    picked = jnp.where((dst < cfg.num_experts * capacity)[:, None],
                       out[jnp.minimum(dst, cfg.num_experts * capacity - 1)],
                       0.0)
    y = (picked.reshape(t, cfg.top_k, d)
         * gates[..., None]).sum(axis=1)                          # (T, D)
    return y, aux


def moe_apply_dense(p, cfg: MoEConfig, x):
    """Auto-shardable MoE. x (B, S, D) -> (B, S, D), plus aux loss."""
    b, s, d = x.shape
    xf = x.reshape(b * s, d)
    capacity = int(np.ceil(b * s * cfg.top_k / cfg.num_experts
                           * cfg.capacity_factor))
    capacity = max(8, -(-capacity // 8) * 8)
    y, aux = _dispatch_combine(p, cfg, xf, capacity)
    if cfg.num_shared:
        shared_cfg = FFNConfig(d, cfg.d_expert * cfg.num_shared, act=cfg.act)
        y = y + ffn_apply(p["shared"], shared_cfg, xf)
    return y.reshape(b, s, d), aux


def moe_apply_tp_shard_map(p, cfg: MoEConfig, x, mesh, *, tp_axis="model",
                           sp_axis="data"):
    """Tensor-parallel experts — the E < mesh_axis case (mixtral: 8 experts
    on a 16-way 'model' axis, so EP cannot shard them).

    Megatron pattern: tokens are ALL-GATHERED over tp (in_spec demands full
    S per rank), every rank dispatches identically (routing is cheap and
    replicated), computes its F/tp slice of every expert it hosts, and the
    partial down-projections are REDUCE-SCATTERED back to the S-sharded
    residual (psum_scatter) — one AG + one RS of (tokens × d_model) per MoE
    layer, the classic TP collective pair, visible in the dry-run HLO."""
    assert cfg.num_shared == 0, "tp expert sharding: shared experts unused"
    b, s, d = x.shape
    sp_axes = (sp_axis,) if isinstance(sp_axis, str) else tuple(sp_axis)
    tp = mesh.shape[tp_axis]
    assert s % tp == 0, (s, tp)

    def local_fn(xl, router_w, experts):
        bl, sl, _ = xl.shape                      # sl == s (full, gathered)
        xf = xl.reshape(bl * sl, d)
        tloc = bl * sl
        capacity = int(np.ceil(tloc * cfg.top_k / cfg.num_experts
                               * cfg.capacity_factor))
        capacity = max(8, -(-capacity // 8) * 8)
        gates, eidx, aux = _route(router_w, cfg, xf)
        flat_e = eidx.reshape(-1)
        onehot = jax.nn.one_hot(flat_e, cfg.num_experts, dtype=jnp.int32)
        pos = (jnp.cumsum(onehot, axis=0) * onehot - 1).max(axis=-1)
        dst = jnp.where(pos < capacity, flat_e * capacity + pos,
                        cfg.num_experts * capacity)
        src = jnp.repeat(jnp.arange(tloc), cfg.top_k)
        buf = jnp.zeros((cfg.num_experts * capacity + 1, d), xf.dtype)
        buf = buf.at[dst].set(xf[src], mode="drop")[:-1]
        buf = buf.reshape(cfg.num_experts, capacity, d)
        # F/tp slice of every expert on this rank
        out = _expert_ffn(experts, cfg, buf)      # partial over F slices
        out = out.reshape(cfg.num_experts * capacity, d)
        picked = jnp.where((dst < cfg.num_experts * capacity)[:, None],
                           out[jnp.minimum(dst, cfg.num_experts * capacity - 1)],
                           0.0)
        y = (picked.reshape(tloc, cfg.top_k, d) * gates[..., None]).sum(axis=1)
        y = y.reshape(bl, sl, d)
        # partial sums over F → reduce-scatter along S back to the residual
        y = jax.lax.psum_scatter(y, tp_axis, scatter_dimension=1, tiled=True)
        aux = jax.lax.pmean(aux, sp_axes)
        return y, aux

    experts_spec = {"w_gate": P(None, None, tp_axis),
                    "w_up": P(None, None, tp_axis),
                    "w_down": P(None, tp_axis, None)}
    fn = jax.shard_map(
        local_fn, mesh=mesh,
        in_specs=(P(sp_axes, None, None), P(None, None), experts_spec),
        out_specs=(P(sp_axes, tp_axis, None), P()),
        check_vma=False)
    return fn(x, p["router"], p["experts"])


def moe_apply_shard_map(p, cfg: MoEConfig, x, mesh, *, ep_axis="model",
                        sp_axis="data"):
    """Production MoE: sequence-parallel routing + expert-parallel compute.

    Token dispatch happens per (sp, ep) shard; expert buffers are exchanged
    with a pair of all_to_alls over the EP axis.  Inside the shard_map the
    code is per-device SPMD — exactly what a hand-written distributed MoE
    runtime does, but in five lines of jax.lax collectives.
    """
    b, s, d = x.shape
    ep = mesh.shape[ep_axis]
    assert cfg.num_experts % ep == 0, (cfg.num_experts, ep)
    sp_axes = (sp_axis,) if isinstance(sp_axis, str) else tuple(sp_axis)

    def local_fn(xl, router_w, experts, shared):
        bl, sl, _ = xl.shape
        xf = xl.reshape(bl * sl, d)
        tloc = bl * sl
        capacity = int(np.ceil(tloc * cfg.top_k / cfg.num_experts
                               * cfg.capacity_factor))
        capacity = max(8, -(-capacity // 8) * 8)
        gates, eidx, aux = _route(router_w, cfg, xf)
        flat_e = eidx.reshape(-1)
        onehot = jax.nn.one_hot(flat_e, cfg.num_experts, dtype=jnp.int32)
        pos = (jnp.cumsum(onehot, axis=0) * onehot - 1).max(axis=-1)
        dst = jnp.where(pos < capacity, flat_e * capacity + pos,
                        cfg.num_experts * capacity)
        src = jnp.repeat(jnp.arange(tloc), cfg.top_k)
        buf = jnp.zeros((cfg.num_experts * capacity + 1, d), xf.dtype)
        buf = buf.at[dst].set(xf[src], mode="drop")[:-1]
        buf = buf.reshape(cfg.num_experts, capacity, d)
        # EP exchange: (E, C, D) -> (E/ep, C*ep, D)
        buf = jax.lax.all_to_all(buf, ep_axis, split_axis=0, concat_axis=1,
                                 tiled=True)
        out = _expert_ffn(experts, cfg, buf)
        # and back: (E/ep, C*ep, D) -> (E, C, D)
        out = jax.lax.all_to_all(out, ep_axis, split_axis=1, concat_axis=0,
                                 tiled=True)
        out = out.reshape(cfg.num_experts * capacity, d)
        picked = jnp.where((dst < cfg.num_experts * capacity)[:, None],
                           out[jnp.minimum(dst, cfg.num_experts * capacity - 1)],
                           0.0)
        y = (picked.reshape(tloc, cfg.top_k, d) * gates[..., None]).sum(axis=1)
        if cfg.num_shared:
            shared_cfg = FFNConfig(d, cfg.d_expert * cfg.num_shared, act=cfg.act)
            y = y + ffn_apply(shared, shared_cfg, xf)
        aux = jax.lax.pmean(aux, sp_axes + (ep_axis,))
        return y.reshape(bl, sl, d), aux

    experts_local_spec = {
        "w_gate": P(ep_axis, None, None),
        "w_up": P(ep_axis, None, None),
        "w_down": P(ep_axis, None, None),
    }
    shared = p.get("shared", {})
    shared_spec = jax.tree.map(lambda _: P(None), shared)
    fn = jax.shard_map(
        local_fn, mesh=mesh,
        in_specs=(P(sp_axes, ep_axis, None), P(None, None),
                  experts_local_spec, shared_spec),
        out_specs=(P(sp_axes, ep_axis, None), P()),
        check_vma=False)
    return fn(x, p["router"], p["experts"], shared)
