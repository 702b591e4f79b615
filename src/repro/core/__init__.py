"""Core: the paper's ParallelMLPs — fused population training via M3 —
plus the paper's §7 future work as first-class citizens: layered (deep,
heterogeneous-depth) populations, feature selection, per-member learning
rates."""
from repro.core.activations import ACTIVATIONS, ACTIVATION_ORDER, PAPER_TEN
from repro.core.lifecycle import HalvingSchedule, compact, survivors
from repro.core.m3 import m3
from repro.core.parallel_mlp import (extract_member, forward, fused_loss, init_params,
                                     member_forward, member_losses, sgd_step)
from repro.core.population import LayeredPopulation, Population

__all__ = [
    "ACTIVATIONS", "ACTIVATION_ORDER", "PAPER_TEN", "m3", "Population",
    "LayeredPopulation", "HalvingSchedule", "compact", "survivors",
    "init_params", "forward", "fused_loss", "member_losses", "sgd_step",
    "extract_member", "member_forward",
]
