"""The main path compiled for a TPU v5e that is described, not attached.

Every kernel of the population engine is compiled by Mosaic for one chip of
a ``v5e:2x2`` topology at the sizes the chip runs: the paper population's
fused hidden width H = 1,280,000 (10,000 members, block 128), a deep
block-128 population for the mid layers, the heads at P = 10,000, and the
int8 serving twins.  The whole paper train step is compiled too and its
``memory_analysis()`` is held to the chip's 16 GB.  Nothing runs: a compile
that passes is not a chip run, but a kernel the chip's compiler refuses
(unlowerable primitive, unaligned block, too much VMEM or SMEM) fails here.

The topology is described inside a module fixture (never at import), and
the tests steer ``ops._resolve_interpret`` with ``monkeypatch``: the CPU
backend would otherwise pick the interpreter.
"""
import os
import pathlib
import re
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import SingleDeviceSharding

from repro.core import deep
from repro.core.activations import PAPER_TEN
from repro.core.population import LayeredPopulation
from repro.kernels import fused_input, ops

HBM_BYTES = 16 * 1000 ** 3     # one TPU v5e chip: 16 GB of HBM
BATCH = 256


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — no TPU compiler installed
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    # a compile for a described chip is written to the persistent cache
    # but cannot be read back without one: keep the cache off meanwhile
    from jax.experimental.compilation_cache import compilation_cache
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)


@pytest.fixture(autouse=True)
def compiled_kernels(monkeypatch):
    monkeypatch.setattr(ops, "_resolve_interpret", lambda interpret: False)


@pytest.fixture(scope="module")
def paper_lp():
    from repro.configs import get_arch
    return get_arch("parallelmlp-10k").model.layered()


@pytest.fixture(scope="module")
def deep_lp():
    return LayeredPopulation(
        100, 2, ((512, 256), (256, 128, 64), (384,), (128,)) * 4,
        tuple(PAPER_TEN[i % 10] for i in range(16)), block=128).sorted()


def _compile(fn, sharding, *args):
    abstract = [jax.tree.map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=sharding),
        a) for a in args]
    return jax.jit(fn).lower(*abstract).compile()


def _sds(shape, dtype=jnp.float32):
    return jax.ShapeDtypeStruct(shape, dtype)


def _n_kernels(compiled) -> int:
    return compiled.as_text().count("tpu_custom_call")


def _input_layer(one_chip, p0, batch, features, with_x):
    """The fused input layer, forward with its g' residual and the one-pass
    backward, compiled at ``p0``'s width: the gradient over W_in and b_in
    (a training step's) or over x too."""
    h = p0.total_hidden

    def fwd_bwd(x, w, b, dy):
        def f(*a):
            xx, ww, bb = a if with_x else (x, *a)
            return ops.fused_input(xx, ww, bb, p0.block_act_ids,
                                   p0.hidden_mask, block=128)
        y, vjp = jax.vjp(f, *((x, w, b) if with_x else (w, b)))
        return y, vjp(dy)

    return _compile(fwd_bwd, one_chip, _sds((batch, features)),
                    _sds((h, features)), _sds((h,)), _sds((batch, h)))


def _bias_reduces(compiled, h) -> int:
    """XLA reduces to an (H,) row: the bias gradient's Σ_b dy·g', before
    the backward kernel made it."""
    return len(re.findall(rf"= f32\[{h}\]\S* reduce\(", compiled.as_text()))


def _bwd_outputs(compiled) -> int:
    """Arrays the compiled backward kernel returns: dW_in, db_in [, dx]."""
    line = next(ln for ln in compiled.as_text().splitlines()
                if "fused_input_bwd" in ln and "custom-call(" in ln)
    return line.split(" custom-call(")[0].count("f32[")


def test_fused_input_fwd_bwd_paper_width(one_chip, paper_lp):
    """The input layer at H = 1,280,000, forward with its g' residual and
    the one-pass backward (whose on-chip memory does not grow with H): two
    kernels, the bias gradient made inside the backward, so no XLA reduce
    over (B, H) is left, and dx made because x is differentiated."""
    p0 = paper_lp.layer_pop(0)
    assert p0.total_hidden == 1_280_000
    c = _input_layer(one_chip, p0, BATCH, 100, with_x=True)
    assert _n_kernels(c) == 2
    assert _bias_reduces(c, p0.total_hidden) == 0
    assert _bwd_outputs(c) == 3


@pytest.mark.parametrize("batch", [256, 32])
def test_fused_input_deep_layer0(one_chip, batch):
    """deep-1k's layer 0 (1,024 members of 512/256/384/128 first-layer
    units, H = 327,680) at both of its batches, differentiated as a
    training step differentiates it: two kernels, no (B, H) reduce, and a
    backward that returns dW_in and db_in only."""
    widths = ((512, 256), (256, 128, 64), (384,), (128,))
    members = [(widths[i % 4], PAPER_TEN[(i // 4) % 10])
               for i in range(1024)]
    lp = LayeredPopulation(100, 2, tuple(w for w, _ in members),
                           tuple((a,) * len(w) for w, a in members),
                           block=128).sorted()
    p0 = lp.layer_pop(0)
    assert p0.total_hidden == 327_680
    c = _input_layer(one_chip, p0, batch, 100, with_x=False)
    assert _n_kernels(c) == 2
    assert _bias_reduces(c, p0.total_hidden) == 0
    assert _bwd_outputs(c) == 2


@pytest.mark.parametrize("features", [128, 784])
def test_fused_input_small_batch_wide_features(one_chip, paper_lp, features):
    """Batch 8 at H = 1,280,000 with x differentiated: a block brings more
    bytes of w_in and dW_in (block_f = 128 columns) than of the (8, H)
    activations, so the tile is sized by the weight's columns and the
    backward's blocks, dx's among them, fit the chip's VMEM."""
    p0 = paper_lp.layer_pop(0)
    assert fused_input.tile_plan(8, p0.total_hidden, features, 128).g == 32
    c = _input_layer(one_chip, p0, 8, features, with_x=True)
    assert _n_kernels(c) == 2
    assert _bwd_outputs(c) == 3


@pytest.mark.parametrize("block_b", [128, 256])
def test_fused_layer_fwd_bwd_block128(one_chip, deep_lp, block_b):
    """Every mid layer of a deep heterogeneous block-128 population, all
    ten activations in the epilogue, forward and two-level backward."""
    params = deep.abstract_params(deep_lp)

    def fwd_bwd(mid, h0, dy):
        def f(mid, h0):
            h = h0
            for l in range(deep_lp.depth - 1):
                h = deep.block_diag_fused(h, mid[l]["w"], deep_lp, l,
                                          bias=mid[l]["b"], block_b=block_b)
            return h
        y, vjp = jax.vjp(f, mid, h0)
        return y, vjp(dy)

    h_in = deep_lp.layer_pop(0).total_hidden
    h_out = deep_lp.layer_pop(deep_lp.depth - 1).total_hidden
    c = _compile(fwd_bwd, one_chip, params["mid"], _sds((BATCH, h_in)),
                 _sds((BATCH, h_out)))
    assert _n_kernels(c) == 2 * (deep_lp.depth - 1)


def _loss_head_fwd_bwd(pop, one_chip, o=2):
    def fwd_bwd(h, w, b, y):
        per, vjp = jax.vjp(lambda h, w, b: ops.loss_head(
            h, w, b, y, pop.block_segment_ids, block_h=128), h, w, b)
        return per, vjp(jnp.ones_like(per))

    return _compile(fwd_bwd, one_chip, _sds((BATCH, pop.total_hidden)),
                    _sds((o, pop.total_hidden)), _sds((pop.num_members, o)),
                    _sds((BATCH,), jnp.int32))


# the head's own temporaries: dlogits stored lane-dense are P·O·B f32
# (20.5 MB at P = 10,000, B = 256); with the classes padded to 128 lanes
# they were 1.31 GB
HEAD_TEMP_BYTES = 64 * 1024 * 1024


def test_loss_head_paper_members(one_chip, paper_lp):
    """Projection + softmax-XE + dlogits at P = 10,000 (the seg table
    rides scalar prefetch), forward and backward."""
    pop = paper_lp.layer_pop(0)
    c = _loss_head_fwd_bwd(pop, one_chip)
    assert _n_kernels(c) == 2
    assert c.memory_analysis().temp_size_in_bytes <= HEAD_TEMP_BYTES


def test_loss_head_deep_last_layer(one_chip):
    """The head over deep-1k's last layer at B = 256: 1,024 members of 1,
    2 and 3 blocks (1,792 blocks), so members span grid-step tiles."""
    widths = ((512, 256), (256, 128, 64), (384,), (128,)) * 256
    acts = tuple((PAPER_TEN[(i // 4) % 10],) * len(w)
                 for i, w in enumerate(widths))
    lp = LayeredPopulation(100, 2, widths, acts, block=128).sorted()
    pop = lp.layer_pop(lp.depth - 1)
    assert pop.total_hidden // 128 == 1792
    c = _loss_head_fwd_bwd(pop, one_chip)
    assert _n_kernels(c) == 2
    assert c.memory_analysis().temp_size_in_bytes <= HEAD_TEMP_BYTES


@pytest.mark.parametrize("o", [100, 355])
def test_loss_head_many_classes(one_chip, paper_lp, o):
    """The head at P = 10,000 one-block members (the paper grid's layout)
    with helena's 100 classes and dionis's 355, forward and backward.  Its
    temporaries grow with O only through the carry entering each tile,
    nt·O·B f32 (65.5 MB at O = 100, 233 MB at O = 355, nt = 625 tiles of
    16 blocks): held to an eighth of the P·O·B f32 that dlogits stored per
    block would take (1.02 GB at O = 100, 3.64 GB at O = 355), plus the
    O = 2 allowance."""
    pop = paper_lp.layer_pop(0)
    c = _loss_head_fwd_bwd(pop, one_chip, o)
    assert _n_kernels(c) == 2
    dlogits = pop.num_members * o * BATCH * 4
    assert (c.memory_analysis().temp_size_in_bytes
            <= dlogits // 8 + HEAD_TEMP_BYTES)


@pytest.mark.parametrize("log_probs", [False, True])
def test_infer_head_paper_members(one_chip, paper_lp, log_probs):
    pop = paper_lp.layer_pop(0)

    def fwd(h, w, b):
        return ops.infer_head(h, w, b, pop.block_segment_ids, block_h=128,
                              log_probs=log_probs)

    c = _compile(fwd, one_chip, _sds((BATCH, pop.total_hidden)),
                 _sds((2, pop.total_hidden)), _sds((10_000, 2)))
    assert _n_kernels(c) == 1


def test_int8_serving_twins(one_chip, deep_lp):
    """The int8 serving forward: all three fused-dequant twins (input,
    mid layers, head) in one program."""
    from repro.quant import quantize_population
    qparams = jax.eval_shape(lambda p: quantize_population(p, deep_lp),
                             deep.abstract_params(deep_lp))

    def serve(q, x):
        return deep.forward(q, x, deep_lp, bd_impl="fused", infer=True,
                            weights_dtype="int8")

    c = _compile(serve, one_chip, qparams, _sds((BATCH, 100)))
    assert _n_kernels(c) == deep_lp.depth + 1


@pytest.mark.parametrize("bd_impl", ["einsum", "fused"])
def test_paper_train_step_fits_one_chip(one_chip, paper_lp, bd_impl):
    """One scanned chunk of the paper population (10,000 members, batch
    256, plain SGD) through the driver's default XLA path and through the
    fused kernels, held to one chip's HBM."""
    from repro.optim import sgd
    chunk = deep.make_population_train_step(
        paper_lp, optimizer=sgd(), bd_impl=bd_impl, scan_steps=2,
        donate_batch=True)
    params = deep.abstract_params(paper_lp)
    opt_state = jax.eval_shape(sgd().init, params)
    c = _compile(chunk, one_chip, params, opt_state,
                 _sds((2, BATCH, 100)), _sds((2, BATCH), jnp.int32),
                 _sds((), jnp.float32))
    mem = c.memory_analysis()
    total = (mem.temp_size_in_bytes + mem.argument_size_in_bytes
             + mem.output_size_in_bytes - mem.alias_size_in_bytes)
    assert total < HBM_BYTES, mem
    if bd_impl == "fused":
        assert _n_kernels(c) >= 4


def test_paper_train_step_member_sharded_four_chips(topo, one_chip,
                                                   paper_lp):
    """The fused paper chunk with members over 'model' on the whole
    v5e:2x2 host: XLA cannot partition a Mosaic kernel, so the kernels run
    under shard_map — no all-gather of weights or activations enters the
    program, and each chip holds a quarter of the population."""
    from jax.sharding import Mesh

    from repro.distributed.sharding import (population_batch_shardings,
                                            population_shardings)
    from repro.optim import sgd
    sys.path.insert(0, str(pathlib.Path(__file__).resolve().parents[1]))
    from chip_smoke import count_collectives

    mesh = Mesh(np.asarray(topo.devices).reshape(1, 4), ("data", "model"),
                axis_types=(jax.sharding.AxisType.Auto,) * 2)
    lp = paper_lp.shard_pad(4)
    opt = sgd()
    chunk = deep.make_population_train_step(lp, optimizer=opt,
                                            bd_impl="fused", scan_steps=2,
                                            donate_batch=True)
    params = deep.abstract_params(lp)
    psh = population_shardings(lp, mesh)
    sh_x, sh_y = population_batch_shardings(mesh, BATCH)
    rep = jax.sharding.NamedSharding(mesh, jax.sharding.PartitionSpec())
    args = (jax.tree.map(lambda a, s: jax.ShapeDtypeStruct(
                a.shape, a.dtype, sharding=s), params, psh),
            jax.tree.map(lambda a: jax.ShapeDtypeStruct(
                a.shape, a.dtype, sharding=rep),
                jax.eval_shape(opt.init, params)),
            jax.ShapeDtypeStruct((2, BATCH, 100), jnp.float32,
                                 sharding=sh_x),
            jax.ShapeDtypeStruct((2, BATCH), jnp.int32, sharding=sh_y),
            0.01)
    with jax.set_mesh(mesh):
        c = chunk.lower(*args).compile()
    counts = count_collectives(c.as_text())
    assert counts["tpu_custom_call"] == 4, counts
    assert counts["all_gather"] == 0, counts
    mem = c.memory_analysis()
    assert (mem.temp_size_in_bytes + mem.argument_size_in_bytes
            + mem.output_size_in_bytes) < HBM_BYTES / 4, mem


def test_block_not_lane_aligned_fails_loudly():
    lp = LayeredPopulation(4, 2, ((5,), (3,)), ("relu", "tanh"), block=8)
    p0 = lp.layer_pop(0)
    x = jnp.zeros((8, 4))
    w = jnp.zeros((p0.total_hidden, 4))
    with pytest.raises(ValueError, match="--population-block"):
        ops.fused_input(x, w, jnp.zeros(p0.total_hidden), p0.block_act_ids,
                        p0.hidden_mask, block=8)
