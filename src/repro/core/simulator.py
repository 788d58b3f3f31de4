"""Domino cycle/energy simulator.

Two layers of fidelity, cross-validated in tests:

1. ``COMGridSim`` — functional simulation of one layer's *compiled block
   chain* (``repro.core.program``): IFM rows stream through RIFMs, PEs fire
   MACs, ROFMs add partial sums on the move, queue group-sums in bounded
   buffers, partial sums accumulate across chained C-blocks (C > N_C),
   outputs concatenate across M-blocks (M > N_M), and the last tile applies
   the M-type activation. Handles conv and FC layers at real VGG scale.
   Produces (a) the exact layer output (validated against a reference conv
   / NumPy FC) and (b) event counts (hops, adds, buffer ops).

2. ``DominoModel`` — analytic event counts for full networks (VGG-11/16/19,
   ResNet-18) feeding the Tab. III energy model; reproduces Tab. IV
   (exec time, throughput, power breakdown, CE) with the paper's
   normalization. Event-count formulas are asserted against COMGridSim on
   small layers.

Model assumptions (documented in EXPERIMENTS.md; calibrated constants below):
  * FDM_FACTOR=16: 160MHz peripheral clock over the 10MHz instruction step
    (paper §IV-A) gives 16 packet lanes per step -> 16 images in flight.
  * steady-state rate: one output row per period p=2(P+W); per network copy,
    one image every max_l(H_out·W_out) cycles.
  * PIPELINE_EFF: layer rate-mismatch stalls.
  * NoC wire+register energy per bit-hop (Noxim-class 45nm estimate).
"""
from __future__ import annotations

import math
import warnings
from collections import defaultdict
from dataclasses import dataclass, field
from functools import lru_cache
from typing import Dict, List, Optional, Tuple

import numpy as np

from repro.core import energy as E
from repro.core.arch import DEFAULT_ARCH, ArchSpec
from repro.core.isa import Buf, CInstr, Dir, Func, MInstr, Sum
from repro.core.mapping import (
    N_C,
    N_M,
    TILES_PER_CHIP,
    ConvSpec,
    FCSpec,
    TileAlloc,
    tiles_for,
    total_chips,
)
from repro.core.schedule import (
    compile_conv_tile,
    compile_last_row_mtype,
    conv_period,
    conv_period_cols,
)

# Deprecated aliases of DEFAULT_ARCH fields — new code takes an ``ArchSpec``.
FDM_FACTOR = DEFAULT_ARCH.fdm_factor
PIPELINE_EFF = DEFAULT_ARCH.pipeline_eff
SKIP_STALL = DEFAULT_ARCH.skip_stall
LINK_PJ_PER_BIT = DEFAULT_ARCH.energy.link_pj_per_bit  # NoC pJ per bit-hop


# ---------------------------------------------------------------------------
# 1. Cycle-stepped COM simulation of one layer's compiled block chain
# ---------------------------------------------------------------------------

# bound on the gathered conv MAC-operand grid per einsum (the oy axis is
# processed in row chunks of at most this many bytes; results and event
# counts are chunking-invariant)
_CONV_CHUNK_BYTES = 32e6


def run_conv_block_chain(lp, w: np.ndarray, x: np.ndarray,
                         residual: Optional[np.ndarray] = None) -> np.ndarray:
    """Execute one conv layer's compiled block chain, batched over a leading
    image axis: ``(B, H, W, C) -> (B, H_out, W_out, M)`` float64.

    This is THE block-chain semantics — partial sums accumulate across
    chained C-blocks, outputs concatenate across M-blocks, the last C-block
    joins the ``residual`` ``(B, H_out, W_out, M)`` where it is given (the
    M-type Bp) and activates as the layer says — shared by ``COMGridSim``
    (B=1 cycle-level cross-validation)
    and ``repro.core.executor.ProgramExecutor`` (whole-program batched
    runs). Each block evaluates as one full-image einsum vectorized over
    the ``oy`` axis; the gather is chunked over ``oy`` to bound the MAC
    operand grid (``_CONV_CHUNK_BYTES``) — results are chunking-invariant.
    """
    L = lp.layer
    K, P, S = L.k, L.padding, L.stride
    B, H, W, C = x.shape
    Ho, Wo, M = L.h_out, L.w_out, L.c_out
    xp = np.pad(x.astype(np.float64), ((0, 0), (P, P), (P, P), (0, 0)))
    out = np.empty((B, Ho, Wo, M))
    # gather indices: patches[b, oy, kr, ox, kc, c] is the MAC operand
    # grid — the oy loop of the per-row walk, vectorized. The gather
    # copies K² slices of the padded IFM, so chunk the oy axis to keep
    # the operand bounded (~32 MB) on big feature maps (224² inputs
    # would otherwise materialize a >200 MB grid at once).
    row_idx = np.arange(Ho)[:, None] * S + np.arange(K)[None, :]
    col_idx = np.arange(Wo)[:, None] * S + np.arange(K)[None, :]
    bytes_per_row = B * K * Wo * K * C * 8
    chunk = max(1, min(Ho, int(_CONV_CHUNK_BYTES // max(bytes_per_row, 1))))
    for y0 in range(0, Ho, chunk):
        patches = xp[:, row_idx[y0:y0 + chunk, :, None, None],
                     col_idx[None, None, :, :], :]
        for mi in range(lp.m_blocks):
            acc = None
            for ci in range(lp.c_blocks):
                blk = lp.block(ci, mi)
                (cs, ce), (ms, me) = blk.c_range, blk.m_range
                # this block's K² chain: PE MACs + kernel-row psum
                # chain (E) + group-sum chain (S), a row-chunk at once
                part = np.einsum(
                    "byrxkc,rkcm->byxm",
                    patches[..., cs:ce], w[:, :, cs:ce, ms:me],
                )
                acc = part if acc is None else acc + part
            # chain closed: the last C-block's M-type tile joins the
            # shortcut, then activates
            if residual is not None:
                acc = acc + residual[:, y0:y0 + chunk, :, ms:me]
            out[:, y0:y0 + chunk, :, ms:me] = _activate(L, acc)
    return out


def _activate(layer, acc: np.ndarray) -> np.ndarray:
    return np.maximum(acc, 0.0) if layer.activation == "relu" else acc


def pool_np(layer: ConvSpec, x: np.ndarray) -> np.ndarray:
    """The pooling after ``layer`` on ``(B, H, W, C)``: the max pool (the
    M-type CMP chain; window ``pool_k``, stride ``pool_stride``, ``-inf``
    beyond a border of ``pool_padding``), then the global average pool
    (MUL) to ``(B, 1, 1, C)``. Neither is part of ``COMGridSim``'s
    output, which is the activation before the pool."""
    if layer.pool_k > 0:
        k, s, p = layer.pool_k, layer.pool_stride, layer.pool_padding
        if p:
            x = np.pad(x, ((0, 0), (p, p), (p, p), (0, 0)),
                       constant_values=-np.inf)
        B, H, W, C = x.shape
        Ho, Wo = (H - k) // s + 1, (W - k) // s + 1
        out = None
        for i in range(k):
            for j in range(k):
                v = x[:, i:i + (Ho - 1) * s + 1:s, j:j + (Wo - 1) * s + 1:s, :]
                out = v if out is None else np.maximum(out, v)
        x = out
    if layer.avg_pool:
        x = x.mean(axis=(1, 2), keepdims=True)
    return x


def conv_block_events(lp, arch: ArchSpec) -> Events:
    """Per-image event counts of one conv layer's block-chain execution.

    Recounted from the explicit block grid (NOT copied from the closed
    forms), uniform over the grid — a CIM array fires whole rows/cols, so
    ragged last blocks hold zeros — exactly the ``batched_layer_events``
    convention, independent of execution chunking or batch size.
    """
    L = lp.layer
    K, P = L.k, L.padding
    Ho, W = L.h_out, L.w_in
    px = Ho * L.w_out
    # on-chip value widths come from the compiled block partition (equal to
    # min(channels, arch geometry) at the default blocking; custom-blocked
    # searched programs carry narrower slices)
    (cs, ce), (ms, me) = lp.block(0, 0).c_range, lp.block(0, 0).m_range
    m_bits = (me - ms) * 8
    c_bits = (ce - cs) * 8
    ev = Events()
    for mi in range(lp.m_blocks):
        for ci in range(lp.c_blocks):
            chain_adds = px * (K * K + K - 1)
            ev.pe_macs += px * K * K
            ev.adds += chain_adds
            ev.ps_hops += chain_adds
            ev.ps_bits += chain_adds * m_bits
            # row end: every kernel row queues one group-sum
            # (WR_BUF/PUSH) popped by the S-direction combine
            ev.buf_push += px * K
            ev.buf_pop += px * K
            if ci > 0:
                # cross-block handoff: the chained C-block receives the
                # previous block's partial sum (ADD_RX) per output px
                ev.ps_hops += px
                ev.ps_bits += px * m_bits
                ev.adds += px
        ev.act += px
        if L.pool_k > 0:
            # fused pooling: the M-type CMP chain compares every window
            # value once per pooled output (energy-model event)
            ev.pool_cmp += (px // max(L.pool_stride ** 2, 1)) * L.pool_k ** 2
    # IFM streaming: each input row segment visits one C-block's K²
    # chain once per output row (in-buffer shift gives K-row reuse);
    # M-blocks of the same C-slice share the stream
    ev.ifm_hops += lp.c_blocks * Ho * K * K * (W + 2 * P)
    ev.ifm_bits += lp.c_blocks * Ho * K * K * (W + 2 * P) * c_bits
    # every output row is one schedule period p = 2(P+W); the block
    # grid pipelines in parallel planes and does not slow the stream
    ev.cycles += Ho * conv_period(L)
    return ev


def run_fc_block_chain(lp, w: np.ndarray, x: np.ndarray) -> np.ndarray:
    """Execute one FC layer's systolic block columns, batched over a leading
    image axis: ``(B, C_in) -> (B, C_out)`` float64.

    Each M-block is a column of chained C-block rows, each row adding its
    MVM slice to the arriving sum (ADD_RX | ADD_PE) and forwarding S; the
    last row activates (M-type ACT) where the layer has an activation.
    Shared by ``COMGridSim`` and ``ProgramExecutor`` — see
    :func:`run_conv_block_chain`.
    """
    L = lp.layer
    x = x.astype(np.float64)
    out = np.empty((x.shape[0], L.c_out))
    for mi in range(lp.m_blocks):
        acc = None
        for ci in range(lp.c_blocks):
            blk = lp.block(ci, mi)
            (cs, ce), (ms, me) = blk.c_range, blk.m_range
            part = x[:, cs:ce] @ w[cs:ce, ms:me]
            acc = part if acc is None else acc + part
        (ms, me) = lp.block(0, mi).m_range
        out[:, ms:me] = _activate(L, acc)
    return out


def fc_block_events(lp, arch: ArchSpec) -> Events:
    """Per-image event counts of one FC layer's systolic column execution
    (recounted from the block grid; see :func:`conv_block_events`)."""
    L = lp.layer
    (cs, ce), (ms, me) = lp.block(0, 0).c_range, lp.block(0, 0).m_range
    m_bits = (me - ms) * 8
    c_bits = (ce - cs) * 8
    ev = Events()
    for _mi in range(lp.m_blocks):
        for ci in range(lp.c_blocks):
            ev.pe_macs += 1       # one MVM vector op per block
            ev.ifm_hops += 1      # IFM slice into this row
            ev.ifm_bits += c_bits
            if ci > 0:            # arriving column sum (ADD_RX)
                ev.ps_hops += 1
                ev.ps_bits += m_bits
                ev.adds += 1
        ev.act += 1
        ev.ps_hops += 1           # column egress hop
        ev.ps_bits += m_bits
    ev.cycles += lp.c_blocks + 2  # fill + egress of the column
    return ev


@dataclass
class Events:
    ps_hops: int = 0          # partial/group-sum tile-to-tile transfers
    ps_bits: int = 0          # bits moved by those hops (actual M channels)
    ifm_hops: int = 0         # IFM segment transfers between RIFMs
    ifm_bits: int = 0         # bits moved (actual C channels)
    adds: int = 0             # ROFM adder firings (per value-vector)
    buf_push: int = 0         # ROFM data-buffer writes (group-sum queue)
    buf_pop: int = 0
    act: int = 0
    pool_cmp: int = 0
    pe_macs: int = 0          # MAC *vector* ops executed by PEs
    cycles: int = 0

    def merge(self, o: "Events"):
        for f in self.__dataclass_fields__:
            setattr(self, f, getattr(self, f) + getattr(o, f))


class COMGridSim:
    """Executes the COM dataflow of one layer's ``CompiledProgram`` block
    chain — conv *or* FC, including multi-block layers (``C > n_c`` and/or
    ``M > n_m``) — following the compiled schedule semantics. Computes real
    outputs and counts events.

    Execution is the explicit ``LayerProgram.blocks`` grid: for every
    M-block (output-channel slice) the partial sums accumulate across the
    chained C-blocks (the cross-block ADD_RX handoff), and the last
    C-block's M-type tile applies the activation; M-block outputs
    concatenate on the output-channel axis. Conv blocks evaluate as one
    full-image einsum vectorized over the ``oy`` axis — every (oy, ox, kr,
    kc) MAC of a block fires at once and the psum / group-sum additions
    reduce over the kc then kr axes, so outputs and event counts are
    identical to the elementwise chain walk while running orders of
    magnitude faster. This is what lets cycle-level simulation
    cross-validate ``reference_conv`` on real VGG-scale layers (e.g. the
    C=512 convs of VGG-16) instead of toy single-block shapes.

    Pooling fused onto a conv layer (``pool_k > 0``) is counted as an
    energy-model event (``pool_cmp``, the M-type CMP chain) but is not part
    of the functional output — the sim returns the pre-pool activation, as
    before. ``repro.core.executor.ProgramExecutor`` applies the pooling
    functionally when chaining layers image→logits.

    The block-chain semantics themselves live in the module-level helpers
    (:func:`run_conv_block_chain` / :func:`run_fc_block_chain` and their
    event counters), batched over a leading image axis and shared with the
    whole-program executor — this class is the single-image, single-layer
    cycle-level view of the same code path.
    """

    def __init__(self, layer, weights: np.ndarray,
                 arch: Optional[ArchSpec] = None, *, program=None):
        from repro.core.program import Workload, compile_program

        if program is None:
            program = compile_program(
                Workload(f"sim:{layer.name}", (layer,)), arch or DEFAULT_ARCH)
        elif arch is not None and arch != program.arch:
            raise ValueError(
                "conflicting architectures: an explicit arch was passed "
                "alongside a program compiled for a different ArchSpec — "
                "recompile the program for the intended arch instead"
            )
        arch = program.arch
        self.program = program
        self.lp = next(
            (lp for lp in program.layer_programs if lp.layer == layer), None)
        if self.lp is None:
            raise KeyError(f"layer {layer.name!r} is not in the program")
        expect = (
            (layer.k, layer.k, layer.c_in, layer.c_out)
            if isinstance(layer, ConvSpec) else (layer.c_in, layer.c_out)
        )
        if weights.shape != expect:
            raise ValueError(
                f"weights shape {weights.shape} != {expect} for {layer.name!r}")
        self.layer = layer
        self.arch = arch
        self.w = weights.astype(np.float64)
        self.ev = Events()

    @classmethod
    def from_program(cls, program, layer_name: str,
                     weights: np.ndarray) -> "COMGridSim":
        """Simulate one layer of a compiled *network* program (the block
        chain, schedules, and event forms all come from the program)."""
        lp = program.layer_program(layer_name)
        return cls(lp.layer, weights, program.arch, program=program)

    def run(self, ifm: np.ndarray) -> np.ndarray:
        """Execute the layer's block chain on a real input.

        Conv: ``(H, W, C) -> (H_out, W_out, M)``; FC: ``(C_in,) ->
        (C_out,)``. Event counts mirror the data movement and match the
        closed forms in ``batched_layer_events`` exactly.
        """
        if isinstance(self.layer, ConvSpec):
            return self._run_conv(ifm)
        return self._run_fc(ifm)

    def _run_conv(self, ifm: np.ndarray) -> np.ndarray:
        out = run_conv_block_chain(self.lp, self.w, ifm[None])[0]
        self.ev.merge(conv_block_events(self.lp, self.arch))
        # the bounded ROFM queues hold at most one group-sum per kernel
        # row: each output step pushes K and pops K
        L = self.layer
        self.max_queue_depth = 1 if (L.h_out > 0 and L.w_out > 0) else 0
        return out

    def _run_fc(self, x: np.ndarray) -> np.ndarray:
        assert x.shape == (self.layer.c_in,)
        out = run_fc_block_chain(self.lp, self.w, x[None])[0]
        self.ev.merge(fc_block_events(self.lp, self.arch))
        self.max_queue_depth = 0
        return out


def reference_conv(ifm: np.ndarray, w: np.ndarray, layer: ConvSpec) -> np.ndarray:
    P, S = layer.padding, layer.stride
    x = np.pad(ifm.astype(np.float64), ((P, P), (P, P), (0, 0)))
    Ho, Wo = layer.h_out, layer.w_out
    out = np.zeros((Ho, Wo, layer.c_out))
    for oy in range(Ho):
        for ox in range(Wo):
            patch = x[oy * S : oy * S + layer.k, ox * S : ox * S + layer.k, :]
            out[oy, ox] = np.einsum("klc,klcm->m", patch, w)
    return np.maximum(out, 0.0)


def reference_fc(x: np.ndarray, w: np.ndarray) -> np.ndarray:
    """NumPy FC reference: ``relu(x @ w)`` (matches the FC systolic column
    semantics — ACT fires at the last row)."""
    return np.maximum(x.astype(np.float64) @ w.astype(np.float64), 0.0)


# ---------------------------------------------------------------------------
# 2. Analytic event counts — vectorized closed forms over layer batches
# ---------------------------------------------------------------------------

EVENT_FIELDS: Tuple[str, ...] = tuple(Events.__dataclass_fields__)


@dataclass(frozen=True)
class LayerTable:
    """Columnar (n_layers,) int64 feature arrays for a layer sequence.

    The batched event engine evaluates every per-layer closed form over these
    arrays in one shot (FC rows carry zeros in the conv-only columns); the
    scalar ``conv_events``/``fc_events`` API is a one-row view of the same
    path, so cycle-sim cross-validation covers both.
    """

    is_conv: np.ndarray
    k: np.ndarray
    c_in: np.ndarray
    c_out: np.ndarray
    h_out: np.ndarray
    w_out: np.ndarray
    w_in: np.ndarray
    padding: np.ndarray
    pool_k: np.ndarray
    pool_stride: np.ndarray
    ops: np.ndarray

    @property
    def n_layers(self) -> int:
        return int(self.is_conv.shape[0])


@lru_cache(maxsize=1024)
def layer_table(layers: Tuple) -> LayerTable:
    """Build (and cache, keyed by the frozen layer specs) the feature table."""
    def col(conv_val, fc_val):
        return np.array(
            [conv_val(l) if isinstance(l, ConvSpec) else fc_val(l) for l in layers],
            dtype=np.int64,
        )

    return LayerTable(
        is_conv=np.array([isinstance(l, ConvSpec) for l in layers], dtype=bool),
        k=col(lambda l: l.k, lambda l: 0),
        c_in=col(lambda l: l.c_in, lambda l: l.c_in),
        c_out=col(lambda l: l.c_out, lambda l: l.c_out),
        h_out=col(lambda l: l.h_out, lambda l: 0),
        w_out=col(lambda l: l.w_out, lambda l: 0),
        w_in=col(lambda l: l.w_in, lambda l: 0),
        padding=col(lambda l: l.padding, lambda l: 0),
        pool_k=col(lambda l: l.pool_k, lambda l: 0),
        pool_stride=col(lambda l: l.pool_stride, lambda l: 1),
        ops=col(lambda l: l.ops, lambda l: l.ops),
    )


def batched_layer_events(t: LayerTable, arch: ArchSpec = DEFAULT_ARCH,
                         n_c_eff=None, n_m_eff=None) -> Dict[str, np.ndarray]:
    """Per-layer event counts, (n_layers,) int64 per Events field.

    Same closed forms the scalar API always used — validated against
    COMGridSim — just evaluated as NumPy array expressions over the whole
    layer batch instead of a Python loop per layer. The ``arch`` geometry
    (``n_c`` x ``n_m``) sets the block factors and on-chip value widths;
    ``n_c_eff``/``n_m_eff`` (broadcastable int arrays, e.g. per-layer
    ``(n_layers,)`` or population ``(P, n_layers)``) override them with a
    candidate mapping's actual per-layer blocking — the default ``None``
    path is untouched (bitwise the committed counts).
    """
    conv = t.is_conv
    K = t.k
    K2 = K * K
    nc = arch.n_c if n_c_eff is None else np.asarray(n_c_eff, dtype=np.int64)
    nm = arch.n_m if n_m_eff is None else np.asarray(n_m_eff, dtype=np.int64)
    cb = -(-t.c_in // nc)                  # ceil-div
    mb = -(-t.c_out // nm)
    px = t.h_out * t.w_out
    chains = cb * mb                       # parallel accumulation chains
    m_bits = np.minimum(t.c_out, nm) * 8
    c_bits = np.minimum(t.c_in, nc) * 8
    conv_hops = px * chains * (K2 + K - 1) + px * mb * (cb - 1)
    fc_hops = mb * (cb - 1) + mb           # column accumulation + egress
    ps_hops = np.where(conv, conv_hops, fc_hops)
    ifm_hops = np.where(conv, t.h_out * K2 * (t.w_in + 2 * t.padding) * cb, cb * mb)
    ev = dict(
        ps_hops=ps_hops,
        ps_bits=ps_hops * m_bits,
        ifm_hops=ifm_hops,
        ifm_bits=ifm_hops * c_bits,
        adds=np.where(conv, conv_hops, mb * (cb - 1)),
        buf_push=np.where(conv, px * chains * K, 0),
        buf_pop=np.where(conv, px * chains * K, 0),
        act=np.where(conv, px * mb, mb),
        pool_cmp=np.where(
            conv & (t.pool_k > 0),
            (px // np.maximum(t.pool_stride ** 2, 1)) * t.pool_k ** 2 * mb,
            0,
        ),
        pe_macs=np.where(conv, px * K2 * chains, cb * mb),
        cycles=np.where(conv, t.h_out * conv_period_cols(t.padding, t.w_in), cb + 2),
    )
    return ev


# Bounded like the compile cache (repro.core.cache_stats introspects both)
@lru_cache(maxsize=4096)
def _network_event_totals(layers: Tuple, arch: ArchSpec) -> Dict[str, int]:
    per_layer = batched_layer_events(layer_table(layers), arch)
    return {f: int(per_layer[f].sum()) for f in EVENT_FIELDS}


def network_event_totals(layers: Tuple, arch: ArchSpec = DEFAULT_ARCH) -> Dict[str, int]:
    """Summed per-image event counts, cached per ``(layers, arch)``."""
    return _network_event_totals(layers, arch)


def events_for_layers(layers, arch: ArchSpec = DEFAULT_ARCH) -> Events:
    """Deprecated: compile the workload instead and read its event totals.

    Thin shim over :func:`repro.core.program.compile_program` — the
    returned counts are the program's own ``event_totals`` (bitwise-
    identical integers)::

        program = compile_program(Workload.of(layers), arch)
        totals = program.event_totals
    """
    warnings.warn(
        "events_for_layers() is deprecated; use repro.core.program."
        "compile_program(workload, arch) and read CompiledProgram"
        ".event_totals (or network_event_totals for the raw closed forms)",
        DeprecationWarning, stacklevel=2,
    )
    layers = tuple(layers)
    if not layers:
        return Events()
    from repro.core.program import Workload, compile_program

    return Events(**compile_program(Workload.of(layers), arch).event_totals)


def conv_events(layer: ConvSpec, arch: ArchSpec = DEFAULT_ARCH) -> Events:
    """Closed-form per-image event counts — validated vs COMGridSim.

    Thin scalar wrapper over the batched path (one-row LayerTable).
    """
    return Events(**network_event_totals((layer,), arch))


def fc_events(layer: FCSpec, arch: ArchSpec = DEFAULT_ARCH) -> Events:
    return Events(**network_event_totals((layer,), arch))


def onchip_pj_from_events(ev: Dict[str, "np.ndarray | int | float"],
                          arch: ArchSpec = DEFAULT_ARCH):
    """Tab. III on-chip energy (pJ) from event counts.

    Accepts scalars or broadcastable NumPy arrays, so the same expression
    serves the scalar ``DominoModel`` API and the batched sweep engine.
    Component energies come from ``arch.energy`` and are rescaled to the
    spec's technology corner by ``arch.energy_scale()`` (x1.0 at 45nm/1V).
    """
    en = arch.energy
    # partial-sum movement: wormhole pass-through — wire/register energy
    # per bit-hop + the ROFM adder on arrival (no per-chunk buffering)
    pj = ev["ps_bits"] * en.link_pj_per_bit
    pj = pj + ev["adds"] * arch.n_m * en.adder_pj_8b
    # control + schedule-table read per executed instruction (per hop;
    # clock-gated when no packet in flight)
    pj = pj + (ev["ps_hops"] + ev["ifm_hops"]) * (
        en.rofm_ctrl_pj + en.rifm_ctrl_pj + en.sched_table_pj
    )
    # IFM streaming: wire energy per hop + one RIFM 256B buffer access
    # per K-row reuse window (in-buffer shifting, paper §II-B)
    pj = pj + ev["ifm_bits"] * en.link_pj_per_bit
    pj = pj + (ev["ifm_hops"] / 3.0) * en.rifm_buffer_pj
    # group-sum queueing in the 16KiB ROFM data buffer
    pj = pj + (ev["buf_push"] + ev["buf_pop"]) * en.data_buffer_pj
    # inter-memory computing (Tab. II functions)
    pj = pj + ev["act"] * arch.n_m * en.act_pj_8b
    pj = pj + ev["pool_cmp"] * arch.n_m * en.pool_pj_8b
    return pj * arch.energy_scale()


def offchip_values_img(allocs) -> float:
    """Feature-map values crossing a chip boundary per image (bit-width
    independent; multiply by the precision to get off-chip bits)."""
    vals = 0.0
    for prev, a in zip(allocs, allocs[1:]):
        same_chip = set(prev.chip_ids) & set(a.chip_ids)
        if not same_chip or a.crosses_chip:
            l = prev.layer
            if isinstance(l, ConvSpec):
                vals += l.h_out * l.w_out * l.c_out
            else:
                vals += l.c_out
    return vals


# ---------------------------------------------------------------------------
# 3. Energy/power/CE for full networks
# ---------------------------------------------------------------------------


@dataclass
class PowerBreakdown:
    onchip_w: float
    offchip_w: float
    cim_w: float

    @property
    def total_w(self) -> float:
        return self.onchip_w + self.offchip_w + self.cim_w


class DominoModel:
    """Full-network Domino evaluation (paper Tab. IV columns).

    Consumes a :class:`~repro.core.program.CompiledProgram`: pass one
    directly (its ``arch`` applies; passing a *conflicting* explicit
    ``arch`` raises), or pass a ``Workload``/layer sequence and the model
    compiles it via ``compile_program`` — either way the mapping, block
    partition, and event totals come from the shared compile cache instead
    of being re-derived per consumer.

    ``arch`` carries every architecture knob (geometry, tiles/chip, clocks,
    energy table); ``precision_bits`` overrides ``arch.precision_bits`` for
    backward compatibility with the pre-`ArchSpec` signature.
    """

    def __init__(self, layers, *, arch: Optional[ArchSpec] = None,
                 precision_bits: Optional[int] = None):
        from repro.core.program import CompiledProgram, Workload, compile_program

        if isinstance(layers, CompiledProgram):
            if arch is not None and arch != layers.arch:
                raise ValueError(
                    "conflicting architectures: an explicit arch was passed "
                    "alongside a program compiled for a different ArchSpec — "
                    "recompile the program for the intended arch instead"
                )
            self.program = layers
            arch = layers.arch
        else:
            arch = DEFAULT_ARCH if arch is None else arch
            self.program = compile_program(Workload.of(layers), arch)
        self.workload = self.program.workload
        self.layers = list(self.workload.layers)
        self.arch = arch
        # shared frozen allocations (cached across models of one network
        # x architecture pair — the program IS the cache line)
        self.allocs: List[TileAlloc] = list(self.program.allocs)
        self.n_tiles = self.program.n_tiles
        self.n_chips = self.program.n_chips
        self.bits = arch.precision_bits if precision_bits is None else precision_bits

    # ---- structure ----
    def tiles_per_network(self) -> int:
        return self.n_tiles

    def copies(self, n_chips: Optional[int] = None) -> float:
        """Network replicas on the given chips (>=1). The paper's chip counts
        exceed the minimal mapping because layers feeding pools / skip joins
        are weight-duplicated for synchronization (Fig. 4); duplication uses
        tiles without adding copies, so we conservatively take the geometric
        mean of {1, full-replication}."""
        chips = n_chips or self.n_chips
        return max(1.0, (chips * self.arch.tiles_per_chip) / self.n_tiles)

    # ---- time ----
    def exec_time_us(self) -> float:
        """Latency of one image through the pipe at the instruction step clock."""
        fill = 0.0
        steady = 0.0
        for l in self.layers:
            if isinstance(l, ConvSpec):
                fill += conv_period(l) / 2
                steady = max(steady, float(l.h_out * l.w_out))
            else:
                cb = math.ceil(l.c_in / self.arch.n_c)
                mb = math.ceil(l.c_out / self.arch.n_m)
                fill += cb + mb * 2
        return (steady + fill) / self.arch.step_hz * 1e6

    def bottleneck_px(self) -> float:
        """Steady-state cycles/img: output pixels of the largest conv."""
        return float(max(
            (l.h_out * l.w_out for l in self.layers if isinstance(l, ConvSpec)),
            default=1024,
        ))

    def skip_stall(self) -> float:
        """Residual skip joins (Bp shortcut via the RIFM) stall the pipeline
        while both operands synchronize — "skip operations ... affect
        performances slightly" (§IV-B1); calibrated stall factor."""
        return self.arch.skip_stall if any(
            isinstance(l, ConvSpec) and l.residual_from for l in self.layers
        ) else 1.0

    def throughput_img_s(self, n_chips: Optional[int] = None) -> float:
        per_copy = self.arch.fdm_factor * self.arch.step_hz / self.bottleneck_px()
        return per_copy * self.copies(n_chips) * self.arch.pipeline_eff \
            * self.skip_stall()

    # ---- optional functional cross-check ----
    def functional_forward(self, images, weights, *, backend: str = "numpy",
                           **kwargs):
        """Run the model's compiled program image→logits through the
        whole-program executor (``repro.core.executor``) — an optional
        functional cross-check beside the analytic Tab. IV path. Returns
        the :class:`~repro.core.executor.ExecutionResult`; its per-image
        ``events`` equal this model's ``program.event_totals``."""
        from repro.core.executor import ProgramExecutor

        return ProgramExecutor(
            self.program, weights, backend=backend, **kwargs).run(images)

    # ---- energy ----
    def events(self) -> Events:
        return Events(**self.program.event_totals)

    def onchip_energy_img_j(self) -> float:
        return float(
            onchip_pj_from_events(self.program.event_totals, self.arch)
        ) * 1e-12

    def offchip_bits_img(self) -> float:
        return offchip_values_img(self.allocs) * self.bits

    def offchip_energy_img_j(self) -> float:
        return self.offchip_bits_img() * self.arch.energy.interchip_pj_per_bit \
            * self.arch.energy_scale() * 1e-12

    def total_ops(self) -> float:
        return float(sum(l.ops for l in self.layers))

    # ---- Tab. IV style evaluation against a counterpart ----
    def evaluate(self, e_mac_pj: float, *, n_chips: Optional[int] = None,
                 area_mm2: Optional[float] = None) -> Dict[str, float]:
        """e_mac_pj: substituted CIM array energy per (8b) OP, normalized to
        45nm/1V — the plug-in parameter (paper: 'Domino adopts existing CIM
        arrays', CIM power not listed). ``n_chips``/``area_mm2`` may be pinned
        to the paper's evaluation setup (they encode the substituted CIM
        array area and the sync weight-duplication)."""
        chips = n_chips or self.n_chips
        img_s = self.throughput_img_s(chips)
        e_on = self.onchip_energy_img_j()
        e_off = self.offchip_energy_img_j()
        ops = self.total_ops()
        e_cim = ops * e_mac_pj * 1e-12
        e_total = e_on + e_off + e_cim
        ce = ops / e_total / 1e12  # TOPS/W
        area = area_mm2 if area_mm2 else self.n_tiles * self.arch.tile_area_um2() / 1e6
        return dict(
            exec_us=self.exec_time_us(),
            img_s=img_s,
            power_w=e_total * img_s,
            onchip_w=e_on * img_s,
            offchip_w=e_off * img_s,
            cim_w=e_cim * img_s,
            ce_tops_w=ce,
            ops=ops,
            area_mm2=area,
            thr_tops_mm2=ops * img_s / 1e12 / area,
            img_s_per_core=img_s / (chips * self.arch.tiles_per_chip),
            n_chips=chips,
            n_tiles=self.n_tiles,
        )
