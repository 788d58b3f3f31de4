"""Layer -> tile mapping (paper §III) and chip partitioning.

CONV K x K x C x M  ->  K² x ceil(C/Nc) x ceil(M/Nm) tiles (kernel pixels
unrolled ACROSS tiles, in row-major kernel order — the COM pipeline order).
FC C_in x C_out     ->  ceil(C_in/Nc) x ceil(C_out/Nm) tiles (systolic
column accumulation).

Chips hold ``tiles_per_chip`` tiles (240 in the paper's evaluation, CIM
arrays of 256 x 256); layers are placed greedily in network order and a
layer spanning a chip boundary contributes its IFM/OFM traffic to the
off-chip accounting (paper §IV-B3).

Placement is one pass of the Workload→CompiledProgram compiler
(``repro.core.program.compile_program``); ``map_network`` survives as a
deprecated shim over it. The network constructors (``vgg11_cifar`` ...)
return frozen :class:`~repro.core.program.Workload` objects — immutable
layer sequences, so code written against plain layer lists keeps working.
"""
from __future__ import annotations

import math
import warnings
from dataclasses import dataclass, field
from typing import Dict, List, Optional, Tuple

from repro.core.arch import DEFAULT_ARCH, ArchSpec

# Deprecated aliases of DEFAULT_ARCH fields — new code takes an ``ArchSpec``.
N_C = DEFAULT_ARCH.n_c              # CIM rows
N_M = DEFAULT_ARCH.n_m              # CIM cols
TILES_PER_CHIP = DEFAULT_ARCH.tiles_per_chip


@dataclass(frozen=True)
class ConvSpec:
    name: str
    k: int           # filter size K
    c_in: int
    c_out: int
    h_in: int        # input feature map height
    w_in: int        # width
    stride: int = 1
    padding: int = 1
    pool_k: int = 0   # max pooling after this layer (K_p); 0 = none
    pool_stride: int = 2
    # the residual join: this layer's output is act(conv(x) + the output
    # of layer ``residual_from``), the shortcut added before the activation
    residual_from: Optional[str] = None
    input_from: Optional[str] = None  # the layer it reads; None = previous
    activation: str = "relu"          # "relu" or "none"
    pool_padding: int = 0             # of the max pool, -inf at the border
    # a global average pool over the output map after this layer (the
    # M-type MUL, Tab. II); the event model does not price it
    avg_pool: bool = False

    @property
    def h_out(self) -> int:
        return (self.h_in + 2 * self.padding - self.k) // self.stride + 1

    @property
    def w_out(self) -> int:
        return (self.w_in + 2 * self.padding - self.k) // self.stride + 1

    @property
    def macs(self) -> int:
        return self.h_out * self.w_out * self.k * self.k * self.c_in * self.c_out

    @property
    def ops(self) -> int:
        return 2 * self.macs


@dataclass(frozen=True)
class FCSpec:
    name: str
    c_in: int
    c_out: int
    activation: str = "relu"          # "relu" or "none"

    @property
    def macs(self) -> int:
        return self.c_in * self.c_out

    @property
    def ops(self) -> int:
        return 2 * self.macs


LayerSpec = "ConvSpec | FCSpec"


@dataclass(frozen=True)
class TileAlloc:
    """Immutable: instances are shared through ``map_network_cached``."""

    layer: LayerSpec
    n_tiles: int
    grid: Tuple[int, int, int]      # (K², c_blocks, m_blocks) — conv
    chip_ids: Tuple[int, ...] = ()
    crosses_chip: bool = False


def tiles_for(layer, arch: ArchSpec = DEFAULT_ARCH) -> Tuple[int, Tuple[int, int, int]]:
    cb, mb = arch.block_partition(layer.c_in, layer.c_out)
    if isinstance(layer, ConvSpec):
        return layer.k * layer.k * cb * mb, (layer.k * layer.k, cb, mb)
    return cb * mb, (1, cb, mb)


def greedy_place(layers: List, arch: ArchSpec = DEFAULT_ARCH,
                 faults=None) -> List[TileAlloc]:
    """Greedy in-order placement pass; per-layer allocations w/ chip ids.

    This is the placement *algorithm*; ``repro.core.program
    .compile_program`` is the public entry point that runs (and caches) it
    as part of building a ``CompiledProgram``.

    ``faults`` (a :class:`repro.faults.FaultSet`) switches to the
    fault-aware walk: chips contribute only their longest healthy
    serpentine segment, layers spill past dead tiles/links/chips (the
    off-chip cost model prices every extra crossing), and a bounded fleet
    raises :class:`repro.faults.FaultCapacityError` when the workload no
    longer fits. An empty FaultSet reproduces the pristine placement
    bitwise.
    """
    if faults is not None and not faults.is_empty:
        from repro.faults.place import fault_place

        return fault_place(list(layers), arch, faults)
    tiles_per_chip = arch.tiles_per_chip
    allocs: List[TileAlloc] = []
    chip, used = 0, 0
    for layer in layers:
        n, grid = tiles_for(layer, arch)
        chips: List[int] = []
        left = n
        start_chip = chip
        while left > 0:
            take = min(left, tiles_per_chip - used)
            if take == 0:
                chip += 1
                used = 0
                continue
            chips.append(chip)
            used += take
            left -= take
        allocs.append(
            TileAlloc(layer=layer, n_tiles=n, grid=grid, chip_ids=tuple(chips),
                      crosses_chip=len(set(chips)) > 1 or chips[0] != start_chip)
        )
    # the legality rules this pass used to guarantee only implicitly live
    # in the shared validator now (repro.search.space); asserting them here
    # turns a capacity overflow or span inconsistency into a ValueError
    # instead of a silent mis-mapping (late import: core must not depend
    # on the search package at module load)
    from repro.search.space import validate_allocs

    validate_allocs(allocs, arch)
    return allocs


def map_network(layers: List, arch: ArchSpec = DEFAULT_ARCH) -> List[TileAlloc]:
    """Deprecated: compile the workload instead and read its allocations.

    Thin shim over :func:`repro.core.program.compile_program` — the
    returned allocations are the program's own (bitwise-identical, same
    frozen ``TileAlloc`` objects)::

        program = compile_program(Workload.of(layers), arch)
        allocs = program.allocs
    """
    warnings.warn(
        "map_network() is deprecated; use repro.core.program.compile_program"
        "(workload, arch) and read CompiledProgram.allocs",
        DeprecationWarning, stacklevel=2,
    )
    layers = list(layers)
    if not layers:
        return []
    from repro.core.program import Workload, compile_program

    return list(compile_program(Workload.of(layers), arch).allocs)


def map_network_cached(layers: Tuple, arch: ArchSpec = DEFAULT_ARCH) -> Tuple[TileAlloc, ...]:
    """Legacy cached-mapping accessor, now a view into the compiled program.

    Delegates to :func:`repro.core.program.compile_program` (memoized on
    the ``(workload, arch)`` pair), so repeated calls return the *same*
    frozen allocation tuple — exactly the sharing the sweep engine's
    caches rely on. The default-arg call shares the explicit
    ``DEFAULT_ARCH`` cache line.
    """
    from repro.core.program import Workload, compile_program

    return compile_program(Workload.of(layers), arch).allocs


def total_chips(allocs: List[TileAlloc]) -> int:
    return max(c for a in allocs for c in a.chip_ids) + 1


def weight_bytes(layers: List, precision_bits: int = 8) -> int:
    total = 0
    for l in layers:
        if isinstance(l, ConvSpec):
            total += l.k * l.k * l.c_in * l.c_out
        else:
            total += l.c_in * l.c_out
    return total * precision_bits // 8


# ---------------------------------------------------------------------------
# Prevailing CNNs from the paper's evaluation (Tab. IV)
# ---------------------------------------------------------------------------


def _workload(name: str, layers: List) -> "Workload":  # noqa: F821
    # late import: repro.core.program imports this module at load time
    from repro.core.program import Workload

    return Workload(name, tuple(layers))


def _vgg(cfg: List, h: int, w: int, fc: List[Tuple[int, int]], name: str):
    layers: List = []
    c_in = 3
    for i, v in enumerate(cfg):
        if v == "M":
            # pooling is fused into the preceding conv layer (paper Fig. 4)
            prev = layers[-1]
            layers[-1] = ConvSpec(**{**prev.__dict__, "pool_k": 2})
            h, w = h // 2, w // 2
            continue
        layers.append(ConvSpec(f"{name}.conv{len(layers)}", 3, c_in, v, h, w))
        c_in = v
    for j, (ci, co) in enumerate(fc):
        layers.append(FCSpec(f"{name}.fc{j}", ci, co))
    return layers


def vgg11_cifar() -> "Workload":  # noqa: F821
    return _workload(
        "vgg11-cifar",
        _vgg([64, "M", 128, "M", 256, 256, "M", 512, 512, "M", 512, 512, "M"],
             32, 32, [(512, 4096), (4096, 4096), (4096, 10)], "vgg11"))


def vgg16_imagenet() -> "Workload":  # noqa: F821
    return _workload(
        "vgg16-imagenet",
        _vgg([64, 64, "M", 128, 128, "M", 256, 256, 256, "M",
              512, 512, 512, "M", 512, 512, 512, "M"],
             224, 224, [(512 * 7 * 7, 4096), (4096, 4096), (4096, 1000)], "vgg16"))


def vgg19_imagenet() -> "Workload":  # noqa: F821
    return _workload(
        "vgg19-imagenet",
        _vgg([64, 64, "M", 128, 128, "M", 256, 256, 256, 256, "M",
              512, 512, 512, 512, "M", 512, 512, 512, 512, "M"],
             224, 224, [(512 * 7 * 7, 4096), (4096, 4096), (4096, 1000)], "vgg19"))


def resnet18_cifar() -> "Workload":  # noqa: F821
    """ResNet-18 (CIFAR-10 variant, paper Tab. IV col. [17])."""
    layers: List = [ConvSpec("rn.conv0", 3, 3, 64, 32, 32)]
    h = w = 32
    c = 64
    blockcfg = [(64, 2, 1), (128, 2, 2), (256, 2, 2), (512, 2, 2)]
    for co, nblocks, stride0 in blockcfg:
        for b in range(nblocks):
            s = stride0 if b == 0 else 1
            layers.append(ConvSpec(f"rn.c{co}b{b}a", 3, c, co, h, w, stride=s))
            h, w = layers[-1].h_out, layers[-1].w_out
            layers.append(
                ConvSpec(f"rn.c{co}b{b}b", 3, co, co, h, w,
                         residual_from=f"rn.c{co}b{b}a")  # skip via RIFM shortcut
            )
            c = co
    # the global average pool takes the last 4x4 map to the FC's 512 inputs
    layers[-1] = ConvSpec(**{**layers[-1].__dict__, "avg_pool": True})
    layers.append(FCSpec("rn.fc", 512, 10))
    return _workload("resnet18-cifar", layers)


#: ResNet-50's stages: (bottleneck width, blocks, stride of the first block)
RESNET50_STAGES = ((64, 3, 1), (128, 4, 2), (256, 6, 2), (512, 3, 2))
RESNET50_EXPANSION = 4


def bottleneck_resnet(name: str, *, image: int = 224, stem: int = 64,
                      stages=RESNET50_STAGES,
                      expansion: int = RESNET50_EXPANSION,
                      classes: int = 1000) -> List:
    """The layers of a ResNet v1.5 of bottleneck blocks on ``image`` x
    ``image`` x 3 input, each named ``<name>.<layer>``:
    :func:`resnet50_imagenet` at its published sizes, smaller ones for
    tests.

    A 7x7 stride-2 stem to ``stem`` channels and a 3x3 stride-2 max pool
    with padding 1; then, for each ``(width, blocks, stride)`` of
    ``stages``, that many bottleneck blocks ``1x1 -> 3x3 -> 1x1`` to
    ``width * expansion`` channels, each joining as ``relu(conv(x) +
    shortcut)``. The first block of a stage has a 1x1 projection shortcut
    (``.proj``, no activation); it and the block's 3x3 convolution carry
    the stage's stride (v1.5). A global average pool and an FC to
    ``classes`` logits with no activation end it. Batch norm is folded
    into the weights with a zero shift, so no layer has a bias. Layers
    are in topological order, ``a``, ``b``, ``proj``, ``c`` in a block.
    """
    h = (image - 1) // 2 + 1                       # the stem's output
    layers: List = [ConvSpec(f"{name}.stem", 7, 3, stem, image, image,
                             stride=2, padding=3, pool_k=3, pool_stride=2,
                             pool_padding=1)]
    x, c, h = f"{name}.stem", stem, (h - 1) // 2 + 1   # the block input
    for stage, (width, blocks, stride0) in enumerate(stages, 1):
        out = width * expansion
        for b in range(blocks):
            s = stride0 if b == 0 else 1
            block = f"{name}.s{stage}b{b}"
            ho = (h - 1) // s + 1
            layers += [
                ConvSpec(f"{block}.a", 1, c, width, h, h, padding=0,
                         input_from=x),
                ConvSpec(f"{block}.b", 3, width, width, h, h, stride=s),
            ]
            shortcut = x
            if b == 0:
                shortcut = f"{block}.proj"
                layers.append(ConvSpec(shortcut, 1, c, out, h, h, stride=s,
                                       padding=0, input_from=x,
                                       activation="none"))
            layers.append(ConvSpec(f"{block}.c", 1, width, out, ho, ho,
                                   padding=0, input_from=f"{block}.b",
                                   residual_from=shortcut))
            x, c, h = f"{block}.c", out, ho
    layers[-1] = ConvSpec(**{**layers[-1].__dict__, "avg_pool": True})
    layers.append(FCSpec(f"{name}.fc", c, classes, activation="none"))
    return layers


def resnet50_imagenet() -> "Workload":  # noqa: F821
    """ResNet-50 v1.5 on 224x224x3 ImageNet input (He et al., arXiv
    1512.03385, Table 1, the 50-layer column; v1.5 puts each bottleneck's
    stride on its 3x3 convolution, as torchvision's ``resnet50`` does):
    :func:`bottleneck_resnet` at its published sizes, 3, 4, 6 and 3
    blocks of widths 64, 128, 256 and 512. 53 convolutions and 1 FC,
    4.089 GMAC and 25.50 M weights an image. Departures: batch norm folded
    into the weights with a zero shift (no biases)."""
    return _workload("resnet50-imagenet", bottleneck_resnet("rn50"))


NETWORKS = {
    "vgg11-cifar": vgg11_cifar,
    "vgg16-imagenet": vgg16_imagenet,
    "vgg19-imagenet": vgg19_imagenet,
    "resnet18-cifar": resnet18_cifar,
}

#: networks the executor runs that the sweep grids do not price:
#: ``resolve_network`` finds them, ``available_networks`` does not list them
EXECUTOR_NETWORKS = {
    "resnet50-imagenet": resnet50_imagenet,
}
