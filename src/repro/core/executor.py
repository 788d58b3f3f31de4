"""Whole-program batched executor: image → logits through a CompiledProgram.

``COMGridSim`` cross-validates ONE layer's block chain at cycle level; this
module runs an entire :class:`~repro.core.program.CompiledProgram` end to
end — every layer's ``ceil(C/n_c) × ceil(M/n_m)`` block chain, partial sums
accumulated across C-blocks, outputs concatenated across M-blocks, each
layer's OFM (after the fused M-type pooling, when present) feeding the next
layer's IFM (conv→conv, conv→flatten→FC, FC→FC) or the layer its
``input_from`` names, and a residual join's shortcut (``residual_from``)
added in the join layer's epilogue before its activation — **batched over
a leading image axis**, so one call simulates B images. That turns the
simulator from a per-layer checker into a fast whole-network oracle (the
paper evaluates whole networks, Tab. IV).

Two backends, mirroring the sweep engine:

* ``"numpy"`` — the oracle. Walks the compiled block chains through the
  *shared* block-semantics helpers hoisted out of ``COMGridSim``
  (``run_conv_block_chain`` / ``run_fc_block_chain`` in
  ``repro.core.simulator``) — one code path, two consumers.
* ``"jax"`` — every block matmul/einsum lowered to the Pallas
  ``com_matmul`` kernel (``repro.kernels.com_matmul``): the K-grid
  accumulates the C-block partial-sum chain in the f32 VMEM scratch —
  exactly the COM partial-sum plane — and the ROFM-style epilogue (the
  join's shortcut, then the ReLU) fuses into the last K step before the
  single writeback.
  The whole layer chain jits into one executable; ``interpret=True``
  (automatic off-TPU) runs the same kernel path on CPU CI.

Event accounting is backend-independent: the executor recounts per-image
events from the explicit block grids (the same counters ``COMGridSim``
uses), and a full program run's totals equal ``network_event_totals``.
"""
from __future__ import annotations

import functools
import time
from dataclasses import dataclass
from typing import TYPE_CHECKING, Dict, List, Mapping, Optional, Sequence, Tuple

import numpy as np

from repro.core import spans
from repro.core.mapping import ConvSpec
from repro.core.simulator import (
    EVENT_FIELDS,
    Events,
    conv_block_events,
    fc_block_events,
    pool_np,
    run_conv_block_chain,
    run_fc_block_chain,
)

if TYPE_CHECKING:
    from repro.kernels.com_matmul import MatmulPlan

BACKENDS: Tuple[str, ...] = ("numpy", "jax")
#: a layer's ``activation`` → the kernel's epilogue activation
ACTIVATIONS: Dict[str, Optional[str]] = {"relu": "relu", "none": None}


def default_interpret() -> bool:
    """The jax backend's ``interpret=None`` resolution: Pallas interpret
    mode everywhere except a real TPU. One definition — the executor and
    the benchmark artifact's ``interpret`` flag both read it."""
    import jax

    return jax.default_backend() != "tpu"


def _pooled_hw(layer: ConvSpec) -> Tuple[int, int]:
    """Feature-map height/width after the layer's fused pooling (if any)."""
    h, w = layer.h_out, layer.w_out
    if layer.pool_k > 0:
        k, s, p = layer.pool_k, layer.pool_stride, layer.pool_padding
        h, w = (h + 2 * p - k) // s + 1, (w + 2 * p - k) // s + 1
    if layer.avg_pool:
        h, w = 1, 1
    return h, w


def _input_from(layers, i: int) -> Optional[str]:
    """The name of the layer whose output ``layers[i]`` reads; None for
    the images."""
    src = getattr(layers[i], "input_from", None)
    if src is None and i > 0:
        src = layers[i - 1].name
    return src


def _read_by_name(layers) -> Tuple[set, set]:
    """``(kept, shortcut)``: the layers whose outputs a later layer reads
    by name (``input_from`` or ``residual_from``), which the walk keeps,
    and those read only as a join's shortcut: the projections."""
    inputs = {_input_from(layers, i) for i in range(len(layers))}
    named = {getattr(l, "input_from", None) for l in layers}
    residuals = {getattr(l, "residual_from", None) for l in layers}
    return (named | residuals) - {None}, residuals - inputs - {None}


def _chain_shapes(layers) -> List[Tuple[int, ...]]:
    """Validate that every layer reads a tensor an earlier layer produces
    (the previous layer's output, or the one ``input_from`` names), that
    each join's shortcut (``residual_from``) has the shape of the layer's
    output before its pool, and that every activation is known; return
    the per-layer *input* shapes (without the batch axis)."""
    shapes: List[Tuple[int, ...]] = []
    outs: Dict[str, Tuple[int, ...]] = {}   # OFM shape after pool/flatten
    problems: List[str] = []
    for i, l in enumerate(layers):
        src = _input_from(layers, i)
        prev = outs.get(src) if src is not None else None
        if src is not None and src not in outs:
            problems.append(f"layers[{i}] ({l.name!r}) reads {src!r}, "
                            "which no earlier layer produces")
        if l.activation not in ACTIVATIONS:
            problems.append(f"layers[{i}] ({l.name!r}) has activation "
                            f"{l.activation!r}; known: {list(ACTIVATIONS)}")
        if isinstance(l, ConvSpec):
            want = (l.h_in, l.w_in, l.c_in)
            if prev is not None and prev != want:
                problems.append(
                    f"layers[{i}] ({l.name!r}) expects IFM {want}, but "
                    f"{src!r} produces {prev}"
                )
            res = l.residual_from
            joined = (l.h_out, l.w_out, l.c_out)
            if res is not None and outs.get(res) != joined:
                problems.append(
                    f"layers[{i}] ({l.name!r}) joins the output of {res!r}, "
                    f"{outs.get(res, 'which no earlier layer produces')}, "
                    f"to its own {joined}"
                )
            shapes.append(want)
            outs[l.name] = _pooled_hw(l) + (l.c_out,)
        else:
            want = (l.c_in,)
            if prev is not None:
                got = prev if len(prev) == 1 else (int(np.prod(prev)),)
                if got != want:
                    problems.append(
                        f"layers[{i}] ({l.name!r}) expects {l.c_in} inputs, "
                        f"but {src!r} produces {prev} "
                        f"(flattens to {got[0]})"
                    )
            shapes.append(want)
            outs[l.name] = (l.c_out,)
    if problems:
        raise ValueError(
            "workload is not an executable image→logits chain:\n"
            + "\n".join(problems)
        )
    return shapes


def _weight_shape(layer) -> Tuple[int, ...]:
    if isinstance(layer, ConvSpec):
        return (layer.k, layer.k, layer.c_in, layer.c_out)
    return (layer.c_in, layer.c_out)


def random_weights(program_or_workload, seed: int = 0) -> Dict[str, np.ndarray]:
    """He-scaled random weights for every layer, keyed by layer name.

    Fan-in scaling keeps activations O(1) through deep ReLU chains, so
    float32 kernel runs stay well-conditioned against the float64 oracle.
    """
    from repro.core.program import CompiledProgram

    layers = (program_or_workload.workload.layers
              if isinstance(program_or_workload, CompiledProgram)
              else tuple(program_or_workload))
    rng = np.random.default_rng(seed)
    out: Dict[str, np.ndarray] = {}
    for l in layers:
        shape = _weight_shape(l)
        fan_in = int(np.prod(shape[:-1]))
        out[l.name] = rng.normal(scale=np.sqrt(2.0 / fan_in), size=shape)
    return out


def jax_forward(program, *, interpret: bool,
                block_m: Optional[int] = None, block_n: Optional[int] = None,
                block_k: Optional[int] = None, images: Optional[int] = None):
    """The jax backend's whole layer chain as one pure function.

    ``forward(x, ws)`` maps float32 images ``(B, *input_shape)`` and the
    float32 weights ``ws`` (one per layer, in layer order) to logits, with
    one Pallas ``com_matmul`` call per conv/FC layer. The chain is a walk
    over named tensors: a layer reads the previous layer's output or the
    one its ``input_from`` names, and a join's shortcut (the output its
    ``residual_from`` names) goes into the join layer's kernel, added to
    the accumulator before the activation. ``block_*`` fix that block
    dimension of every kernel; the others are chosen per layer from
    its matmul's shape (:meth:`ProgramExecutor.plan` lists them): the
    shape of the batch ``forward`` is called with, or of a batch of
    ``images`` where that is given. The sharded executor gives the whole
    batch, so that each shard runs the one-device chain's blocks and its
    logits are bitwise the one-device ones. The executor jits it
    (:meth:`ProgramExecutor.lower`); compiling it yourself from abstract
    inputs (``jax.jit(forward).lower(x, ws).compile()``) gives the
    executable's HLO text and memory analysis for a described TPU.
    """
    import contextlib

    import jax
    import jax.numpy as jnp

    from repro.kernels.com_matmul import com_matmul_padded

    layer_programs = program.layer_programs
    layers = program.workload.layers
    kept, shortcut = _read_by_name(layers)
    sources = [_input_from(layers, i) for i in range(len(layers))]
    given = dict(block_m=block_m, block_n=block_n, block_k=block_k)
    blocks = [given] * len(layer_programs)
    if images is not None:
        blocks = [dict(block_m=p.bm, block_n=p.bn, block_k=p.bk)
                  for _, p in matmul_plans(program, images, **given)]

    def matmul(l, x2d, w2d, blk, residual=None):
        # one COM kernel call per layer matmul: the K-grid walks the
        # C-block chain, partial sums riding the f32 VMEM scratch;
        # the join and the activation fuse into the last K step (M-type
        # Bp, then ACT). Blocks not given are chosen from the matmul's
        # shape (``choose_blocks``), on a TPU and in interpret mode alike.
        # The kernel's name is the device op's name in a trace.
        return com_matmul_padded(
            x2d, w2d, activation=ACTIVATIONS[l.activation],
            residual=residual, residual_first=residual is not None, **blk,
            interpret=interpret, name=f"com_matmul_{l.name}",
        )

    def forward(x, ws):
        # a named scope per layer and per step of it: they change the ops'
        # metadata only, so a device trace's ops map back to the layer;
        # a projection's steps are in a ``shortcut`` scope besides
        outs = {}
        for lp, w, blk, src in zip(layer_programs, ws, blocks, sources):
            l = lp.layer
            h = outs[src] if src in outs else x
            with jax.named_scope(l.name), (
                    jax.named_scope("shortcut") if l.name in shortcut
                    else contextlib.nullcontext()):
                if isinstance(l, ConvSpec):
                    y = conv(l, h, w, blk, outs.get(l.residual_from))
                else:
                    if h.ndim > 2:
                        h = h.reshape(h.shape[0], -1)
                    with jax.named_scope("matmul"):
                        y = matmul(l, h, w, blk)
            if l.name in kept:
                outs[l.name] = y
            x = y
        return x

    def conv(l, x, w, blk, residual):
        K, P, S = l.k, l.padding, l.stride
        Ho, Wo = l.h_out, l.w_out
        B = x.shape[0]
        def tap(xp, kr, kc):
            # the input under kernel tap (kr, kc) at every output pixel;
            # a strided one is one lax.slice, where strided indexing
            # would lower to a gather
            if S == 1:
                return xp[:, kr:kr + Ho, kc:kc + Wo, :]
            return jax.lax.slice(
                xp, (0, kr, kc, 0),
                (B, kr + (Ho - 1) * S + 1, kc + (Wo - 1) * S + 1, l.c_in),
                (1, S, S, 1))

        with jax.named_scope("im2col"):
            if K == 1 and P == 0:
                # a 1x1 convolution reads its input as it is, or one
                # strided slice of it
                patches = x if S == 1 else tap(x, 0, 0)
            else:
                xp = jnp.pad(x, ((0, 0), (P, P), (P, P), (0, 0)))
                cols = [tap(xp, kr, kc)
                        for kr in range(K) for kc in range(K)]
                # im2col in (kr, kc, c) order == w.reshape row-major
                patches = jnp.concatenate(cols, axis=-1)
            patches = patches.reshape(B * Ho * Wo, K * K * l.c_in)
        with jax.named_scope("matmul"):
            if residual is not None:
                residual = residual.reshape(B * Ho * Wo, l.c_out)
            y = matmul(
                l, patches, w.reshape(K * K * l.c_in, l.c_out), blk, residual,
            ).reshape(B, Ho, Wo, l.c_out)
        if l.pool_k > 0:
            p = l.pool_padding
            with jax.named_scope("pool"):
                y = jax.lax.reduce_window(
                    y, -jnp.inf, jax.lax.max,
                    (1, l.pool_k, l.pool_k, 1),
                    (1, l.pool_stride, l.pool_stride, 1),
                    ((0, 0), (p, p), (p, p), (0, 0)) if p else "VALID",
                )
        if l.avg_pool:
            with jax.named_scope("pool"):
                y = jnp.mean(y, axis=(1, 2), keepdims=True)
        return y

    return forward


def matmul_plans(program, images: int, *, block_m: Optional[int] = None,
                 block_n: Optional[int] = None, block_k: Optional[int] = None
                 ) -> List[Tuple[str, "MatmulPlan"]]:
    """``(layer name, MatmulPlan)`` for each layer's ``com_matmul_padded``
    call in :func:`jax_forward`'s chain on a batch of ``images``: the
    matmul's ``(M, K, N)``, its blocks, grid steps and padded bytes, and
    whether it joins a shortcut and that shortcut's bytes."""
    from repro.kernels.com_matmul import matmul_plan

    return [(lp.layer.name, matmul_plan(
                *_matmul_dims(lp.layer, images), _ITEMSIZE, block_m=block_m,
                block_n=block_n, block_k=block_k,
                residual=getattr(lp.layer, "residual_from", None) is not None))
            for lp in program.layer_programs]


_ITEMSIZE = np.dtype(np.float32).itemsize   # the chain's dtype


def _matmul_dims(layer, images: int) -> Tuple[int, int, int]:
    """``(M, K, N)`` of ``layer``'s matmul on a batch of ``images``."""
    if isinstance(layer, ConvSpec):
        return (images * layer.h_out * layer.w_out,
                layer.k * layer.k * layer.c_in, layer.c_out)
    return images, layer.c_in, layer.c_out


@dataclass(frozen=True)
class ExecutionResult:
    """One batched program run: outputs + per-image events + timing."""

    outputs: np.ndarray          # (B, c_out_last) logits (post-activation)
    events: Mapping[str, int]    # per-image totals == network_event_totals
    backend: str
    batch: int
    wall_s: float                # the whole run() call, host clock
    n_shards: int = 1            # devices the batch axis was sharded over


class ProgramExecutor:
    """Runs a whole :class:`CompiledProgram` image→logits, batched.

    ``weights`` is a mapping ``layer name → ndarray`` (conv ``(K, K, C,
    M)``, FC ``(C_in, C_out)``) or a sequence aligned with the workload's
    layers. ``backend`` is ``"numpy"`` (shared block-semantics oracle) or
    ``"jax"`` (block einsums lowered to the Pallas ``com_matmul`` kernel,
    whole chain jitted). ``interpret=None`` auto-selects Pallas interpret
    mode off-TPU so CPU CI exercises the real kernel path.

    ``shard`` turns on the multi-device scale-out mode (jax backend only):
    the leading image-batch axis is partitioned across a 1-D ``("data",)``
    mesh via ``shard_map`` — the whole jitted layer chain runs per shard
    and the logits gather at the end. Batches are zero-padded up to a
    multiple of the device count and the pad rows sliced off, so any B
    works. Logits are bitwise-identical to the unsharded jax backend.
    Accepted values: ``None``/``False`` (off), ``"auto"``/``"data"``/
    ``True`` (shard across all visible devices, falling back to the
    single-device path when only one is visible), or an explicit 1-D
    ``("data",)`` ``jax.sharding.Mesh``. ``n_shards`` reports the
    resolved device count (1 = fallback or sharding off).

    Construct via :meth:`CompiledProgram.executor` or call
    :meth:`CompiledProgram.execute` directly.
    """

    def __init__(self, program, weights, *, backend: str = "numpy",
                 interpret: Optional[bool] = None,
                 block_m: Optional[int] = None, block_n: Optional[int] = None,
                 block_k: Optional[int] = None, shard=None, faults=None):
        if backend not in BACKENDS:
            raise ValueError(
                f"unknown executor backend {backend!r}; available: {list(BACKENDS)}")
        self.program = program
        self.backend = backend
        self.interpret = interpret
        self.blocks = (block_m, block_n, block_k)
        layers = program.workload.layers
        self.input_shape = _chain_shapes(layers)[0]
        self.weights = self._resolve_weights(layers, weights)
        # weight-cell faults / tile dropout realize HERE, on the resolved
        # float64 list both backends consume — so the numpy oracle and the
        # Pallas path see byte-identical faulted weights by construction.
        # faults=None inherits the program's own FaultSet (a fault-compiled
        # program executes its faults without restating them).
        self.faults = faults if faults is not None \
            else getattr(program, "faults", None)
        self.fault_info: Optional[Dict[str, float]] = None
        if self.faults is not None and self.faults.has_workload_faults:
            from repro.faults.inject import apply_weight_faults

            self.weights, self.fault_info = apply_weight_faults(
                layers, self.weights, self.faults, program.arch)
        self._events: Optional[Dict[str, int]] = None
        self._jax_chain = None
        self._mesh = self._resolve_shard(shard, backend)

    @staticmethod
    def _resolve_shard(shard, backend):
        """``shard`` → a 1-D ``("data",)`` mesh with >1 device, or None
        (sharding off / single-device fallback)."""
        if shard is None or shard is False:
            return None
        if backend != "jax":
            raise ValueError(
                f"shard={shard!r} requires backend='jax'; the numpy oracle "
                "is single-device by design")
        if shard in ("auto", "data", True):
            from repro.launch.mesh import make_data_mesh

            mesh = make_data_mesh()
        else:
            mesh = shard  # an explicit Mesh
            if "data" not in getattr(mesh, "shape", {}):
                raise ValueError(
                    f"shard={shard!r}: expected 'auto', 'data', True, or a "
                    "1-D ('data',) jax Mesh")
        # auto-fallback: a 1-device mesh runs the plain unsharded path
        return mesh if mesh.shape["data"] > 1 else None

    @property
    def n_shards(self) -> int:
        """Devices the batch axis is sharded over (1 = unsharded)."""
        return int(self._mesh.shape["data"]) if self._mesh is not None else 1

    @staticmethod
    def _resolve_weights(layers, weights) -> List[np.ndarray]:
        if isinstance(weights, Mapping):
            names = [l.name for l in layers]
            if len(set(names)) != len(names):
                raise ValueError(
                    "workload repeats layer names; pass weights as a "
                    "sequence aligned with the layers instead of a dict")
            missing = [n for n in names if n not in weights]
            if missing:
                raise KeyError(f"weights missing for layers {missing}")
            seq: Sequence = [weights[n] for n in names]
        else:
            seq = list(weights)
            if len(seq) != len(layers):
                raise ValueError(
                    f"{len(seq)} weight arrays for {len(layers)} layers")
        out: List[np.ndarray] = []
        for l, w in zip(layers, seq):
            w = np.asarray(w)
            if w.shape != _weight_shape(l):
                raise ValueError(
                    f"weights shape {w.shape} != {_weight_shape(l)} "
                    f"for {l.name!r}")
            out.append(w.astype(np.float64))
        return out

    # ---- event accounting (backend-independent) ----
    @property
    def events(self) -> Dict[str, int]:
        """Per-image event totals, recounted from the explicit block grids
        (the same counters ``COMGridSim`` fires) — equal to
        ``network_event_totals(workload.layers, arch)``."""
        if self._events is None:
            total = Events()
            arch = self.program.arch
            for lp in self.program.layer_programs:
                if isinstance(lp.layer, ConvSpec):
                    total.merge(conv_block_events(lp, arch))
                else:
                    total.merge(fc_block_events(lp, arch))
            self._events = {f: getattr(total, f) for f in EVENT_FIELDS}
        return dict(self._events)

    # ---- input handling ----
    def _batch(self, images) -> np.ndarray:
        x = np.asarray(images, dtype=np.float64)
        want = self.input_shape
        if x.shape == want:                    # single image convenience
            x = x[None]
        if x.ndim != len(want) + 1 or x.shape[1:] != want:
            raise ValueError(
                f"images shape {x.shape} does not match the program's "
                f"input {want} (optionally with a leading batch axis)")
        return x

    # ---- numpy backend: the shared block-semantics oracle ----
    def _run_numpy(self, x: np.ndarray) -> np.ndarray:
        # the same walk over named tensors as jax_forward's; a join's
        # shortcut is added in its last C-block's epilogue
        layers = self.program.workload.layers
        kept, _ = _read_by_name(layers)
        outs: Dict[str, np.ndarray] = {}
        for i, (lp, w) in enumerate(zip(self.program.layer_programs,
                                        self.weights)):
            l = lp.layer
            h = outs.get(_input_from(layers, i), x)
            if isinstance(l, ConvSpec):
                res = outs[l.residual_from] if l.residual_from else None
                y = pool_np(l, run_conv_block_chain(lp, w, h, res))
            else:
                if h.ndim > 2:
                    h = h.reshape(h.shape[0], -1)  # conv→flatten→FC
                y = run_fc_block_chain(lp, w, h)
            if l.name in kept:
                outs[l.name] = y
            x = y
        return x

    # ---- jax backend: block chains lowered to the Pallas COM kernel ----
    def _build_jax(self):
        """``(chain, ws, place)``: ``chain(b)``, the jitted chain for a
        batch of ``b`` images, its float32 weights on the device, and the
        map from a float32 batch to the chain's input (padded and sharded
        in sharded mode)."""
        import jax
        import jax.numpy as jnp

        interpret = self.interpret
        if interpret is None:
            interpret = default_interpret()
        bm, bn, bk = self.blocks
        ws = [jnp.asarray(w, dtype=jnp.float32) for w in self.weights]
        if self._mesh is None:
            jit_forward = jax.jit(jax_forward(
                self.program, interpret=interpret,
                block_m=bm, block_n=bn, block_k=bk))
            return lambda images: jit_forward, ws, lambda x: x

        # sharded mode: the whole layer chain runs per batch shard inside
        # shard_map; logits gather on the ("data",) axis at the end. The
        # chain has no cross-image math, and each shard runs the blocks
        # chosen for the whole batch, as the unsharded path does, so
        # per-image results are bitwise those of the unsharded path.
        from jax.sharding import PartitionSpec as P

        from repro.parallel.sharding import leading_axis_sharding

        mesh = self._mesh
        n_dev = mesh.shape["data"]

        @functools.lru_cache(maxsize=None)
        def chain(images):
            return jax.jit(jax.shard_map(
                jax_forward(self.program, interpret=interpret, block_m=bm,
                            block_n=bn, block_k=bk, images=images),
                mesh=mesh, in_specs=(P("data"), P()),
                out_specs=P("data"), check_vma=False,
            ))

        in_sharding = leading_axis_sharding(mesh, len(self.input_shape) + 1)

        def place(x):
            pad = (-x.shape[0]) % n_dev
            if pad:  # B need not divide the mesh: pad rows are sliced off
                x = jnp.concatenate(
                    [x, jnp.zeros((pad,) + x.shape[1:], x.dtype)])
            return jax.device_put(x, in_sharding)

        return chain, ws, place

    def plan(self, batch: int) -> List[Tuple[str, "MatmulPlan"]]:
        """:func:`matmul_plans` of the jax backend's chain for a batch of
        ``batch`` images, as each device runs it: in sharded mode, its
        share of the batch on the blocks chosen for the whole batch."""
        from repro.kernels.com_matmul import matmul_plan

        bm, bn, bk = self.blocks
        whole = matmul_plans(self.program, batch,
                             block_m=bm, block_n=bn, block_k=bk)
        if self.n_shards == 1:
            return whole
        rows = -(-batch // self.n_shards)   # images on each device
        return [(name, matmul_plan(*_matmul_dims(lp.layer, rows), _ITEMSIZE,
                                   block_m=p.bm, block_n=p.bn, block_k=p.bk,
                                   residual=p.joins))
                for (name, p), lp in zip(whole, self.program.layer_programs)]

    def _jax_args(self, x: np.ndarray):
        import jax.numpy as jnp

        if self._jax_chain is None:
            with spans.span("executor.build") as sp:
                self._jax_chain = self._build_jax()
                sp.count("bytes_weights",
                         sum(w.nbytes for w in self._jax_chain[1]))
                plan = [p for _, p in self.plan(x.shape[0])]
                sp.count("grid_steps", sum(p.steps for p in plan))
                sp.count("pad_bytes_per_call",
                         sum(p.pad_bytes for p in plan))
                sp.count("residual_bytes_per_call",
                         sum(p.residual_bytes for p in plan))
                sp.count("joins", sum(p.joins for p in plan))
        chain, ws, place = self._jax_chain
        jit_forward = chain(x.shape[0])
        with spans.span("executor.upload") as sp:
            xd = place(jnp.asarray(x, dtype=jnp.float32))
            sp.count("bytes_up", xd.nbytes)
        return jit_forward, (xd, ws)

    def lower(self, images):
        """The jax backend's jitted chain lowered for ``images``: the
        trace :meth:`run` dispatches for a batch of that shape. Its
        ``.compile()`` is the executable ``run`` then reuses, with its HLO
        text and memory analysis."""
        if self.backend != "jax":
            raise ValueError("lower() needs backend='jax'")
        jit_forward, args = self._jax_args(self._batch(images))
        return jit_forward.lower(*args)

    def run(self, images) -> ExecutionResult:
        """Execute the whole program on a batch of images → logits.

        With :mod:`repro.core.spans` recording, each call is a root span
        ``executor.run`` over ``executor.batch`` (the float64 conversion),
        then, on the jax backend, ``executor.build`` (first call only;
        besides ``bytes_weights`` it counts, from :meth:`plan` for that
        call's batch, the kernels' ``grid_steps`` on each device, the
        ``pad_bytes_per_call`` of their padded operands, the ``joins``
        and the ``residual_bytes_per_call`` of the shortcuts they read),
        ``executor.upload``, ``executor.dispatch`` (until the jitted chain
        returns) and ``executor.fetch`` (the logits to the host); the
        numpy backend's chain is ``executor.dispatch`` alone."""
        t0 = time.perf_counter_ns()
        with spans.span("executor.run", t0) as call:
            with spans.span("executor.batch") as sp:
                x = self._batch(images)
                sp.count("bytes_host", x.nbytes)
            if self.backend == "numpy":
                with spans.span("executor.dispatch"):
                    out = self._run_numpy(x)
            else:
                jit_forward, args = self._jax_args(x)
                with spans.span("executor.dispatch"):
                    y = jit_forward(*args)
                with spans.span("executor.fetch") as sp:
                    host = np.asarray(y)
                    sp.count("bytes_down", host.nbytes)
                    out = host[:x.shape[0]]
            call.count("images", x.shape[0])
        t1 = call.end_ns if call.end_ns is not None else time.perf_counter_ns()
        return ExecutionResult(
            outputs=out, events=self.events, backend=self.backend,
            batch=x.shape[0], wall_s=(t1 - t0) * 1e-9,
            n_shards=self.n_shards,
        )

    def __call__(self, images) -> np.ndarray:
        return self.run(images).outputs
