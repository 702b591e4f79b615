"""parallelmlp-10k [population] — the paper's OWN experiment as an arch.

10,000 independent MLPs fused into one network (§4.2 of the paper):
hidden sizes 1..100 × 10 activation functions × 10 repeats, 100 input
features, 2 classes.  block=128 aligns every member's hidden slice to the
TPU lane width so M3 lowers to the segment-blocked matmul kernel; block=1
(reduced/CPU) reproduces the paper's exact layout.

Distribution: members shard over the 'model' axis — ZERO cross-member
collectives (the paper's "embarrassingly parallel" becomes literal mesh
locality); batch shards over ('pod','data') with per-member gradient
all-reduce."""
from repro.configs.base import ArchSpec
from repro.core.activations import PAPER_TEN
from repro.core.population import Population

IN_FEATURES = 100
OUT_CLASSES = 2


def config() -> ArchSpec:
    # §Perf iterations (paper cell) — tight packing REFUTED twice:
    #   block 128→8 (130 buckets)            → mem term 7.6→297 ms
    #   block=8 + size-major order (13)      → mem term 7.6→64.5 ms
    # Diagnosis: bucket slice boundaries don't align with the 16-way shard
    # grid of the fused hidden axis, so every slice triggers SPMD
    # rematerialisation.  The paper's ONE-fused-op layout (uniform 128 pad,
    # single bucket einsum) beats tight packing at scale; its 2.5× padding
    # waste lands on the idle compute term.  Kept at 128.
    pop = Population.grid(IN_FEATURES, OUT_CLASSES,
                          hidden_range=range(1, 101),
                          activations=PAPER_TEN,
                          repeats=10, block=128)
    return ArchSpec(
        arch_id="parallelmlp-10k", kind="population", model=pop,
        optimizer="sgd", lr=1e-2,
        skip_shapes=("prefill_32k", "decode_32k", "long_500k"),
        skip_reason="tabular MLP population: LM shapes are not defined; "
                    "the chip benchmark's cells live in chipbench/",
        source="[the reproduced paper, §4.2]",
        notes="10,000 members, total fused hidden = 1,280,000 (128-aligned); "
              "population axis = 'model'.")


def reduced() -> ArchSpec:
    pop = Population.grid(10, 3, hidden_range=range(1, 9),
                          activations=("relu", "tanh"), repeats=2, block=8)
    return ArchSpec(arch_id="parallelmlp-10k", kind="population", model=pop,
                    optimizer="sgd", lr=1e-2)
