"""Whole-program batched executor: image → logits through a CompiledProgram.

``COMGridSim`` cross-validates ONE layer's block chain at cycle level; this
module runs an entire :class:`~repro.core.program.CompiledProgram` end to
end — every layer's ``ceil(C/n_c) × ceil(M/n_m)`` block chain, partial sums
accumulated across C-blocks, outputs concatenated across M-blocks, each
layer's OFM (after the fused M-type pooling, when present) feeding the next
layer's IFM (conv→conv, conv→flatten→FC, FC→FC) — **batched over a leading
image axis**, so one call simulates B images. That turns the simulator from
a per-layer checker into a fast whole-network oracle (the paper evaluates
whole networks, Tab. IV).

Two backends, mirroring the sweep engine:

* ``"numpy"`` — the oracle. Walks the compiled block chains through the
  *shared* block-semantics helpers hoisted out of ``COMGridSim``
  (``run_conv_block_chain`` / ``run_fc_block_chain`` in
  ``repro.core.simulator``) — one code path, two consumers.
* ``"jax"`` — every block matmul/einsum lowered to the Pallas
  ``com_matmul`` kernel (``repro.kernels.com_matmul``): the K-grid
  accumulates the C-block partial-sum chain in the f32 VMEM scratch —
  exactly the COM partial-sum plane — and the ROFM-style epilogue (ReLU,
  optional bias) fuses into the last K step before the single writeback.
  The whole layer chain jits into one executable; ``interpret=True``
  (automatic off-TPU) runs the same kernel path on CPU CI.

Event accounting is backend-independent: the executor recounts per-image
events from the explicit block grids (the same counters ``COMGridSim``
uses), and a full program run's totals equal ``network_event_totals``.
"""
from __future__ import annotations

import time
from dataclasses import dataclass
from typing import Dict, List, Mapping, Optional, Sequence, Tuple

import numpy as np

from repro.core.mapping import ConvSpec
from repro.core.simulator import (
    EVENT_FIELDS,
    Events,
    conv_block_events,
    fc_block_events,
    run_conv_block_chain,
    run_fc_block_chain,
)

BACKENDS: Tuple[str, ...] = ("numpy", "jax")


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
        k, s = layer.pool_k, layer.pool_stride
        h, w = (h - k) // s + 1, (w - k) // s + 1
    return h, w


def _chain_shapes(layers) -> List[Tuple[int, ...]]:
    """Validate that every layer's OFM feeds the next layer's IFM; return
    the per-layer *input* shapes (without the batch axis)."""
    shapes: List[Tuple[int, ...]] = []
    prev: Optional[Tuple[int, ...]] = None  # OFM shape after pooling/flatten
    problems: List[str] = []
    for i, l in enumerate(layers):
        if isinstance(l, ConvSpec):
            if l.residual_from is not None:
                raise NotImplementedError(
                    f"layer {l.name!r} has residual_from={l.residual_from!r}: "
                    "the whole-program executor chains straight-line "
                    "conv/FC programs (VGG-class); residual joins are not "
                    "executed functionally yet"
                )
            want = (l.h_in, l.w_in, l.c_in)
            if prev is not None and prev != want:
                problems.append(
                    f"layers[{i}] ({l.name!r}) expects IFM {want}, but the "
                    f"previous layer produces {prev}"
                )
            shapes.append(want)
            prev = _pooled_hw(l) + (l.c_out,)
        else:
            want = (l.c_in,)
            if prev is not None:
                got = prev if len(prev) == 1 else (int(np.prod(prev)),)
                if got != want:
                    problems.append(
                        f"layers[{i}] ({l.name!r}) expects {l.c_in} inputs, "
                        f"but the previous layer produces {prev} "
                        f"(flattens to {got[0]})"
                    )
            shapes.append(want)
            prev = (l.c_out,)
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


def _maxpool_np(x: np.ndarray, k: int, s: int) -> np.ndarray:
    """Max pool (B, H, W, C) with window k, stride s — the functional twin
    of the M-type CMP chain (``Func.CMP``) the schedule compiler emits."""
    B, H, W, C = x.shape
    Ho, Wo = (H - k) // s + 1, (W - k) // s + 1
    out = None
    for i in range(k):
        for j in range(k):
            v = x[:, i:i + (Ho - 1) * s + 1:s, j:j + (Wo - 1) * s + 1:s, :]
            out = v if out is None else np.maximum(out, v)
    return out


def jax_forward(program, *, interpret: bool,
                block_m: Optional[int] = None, block_n: Optional[int] = None,
                block_k: Optional[int] = None):
    """The jax backend's whole layer chain as one pure function.

    ``forward(x, ws)`` maps float32 images ``(B, *input_shape)`` and the
    float32 weights ``ws`` (one per layer, in layer order) to logits, with
    one Pallas ``com_matmul`` call per conv/FC layer. The executor jits
    it (:meth:`ProgramExecutor.lower`); compiling it yourself from
    abstract inputs (``jax.jit(forward).lower(x, ws).compile()``) gives
    the executable's HLO text and memory analysis for a described TPU.
    """
    import jax
    import jax.numpy as jnp

    from repro.kernels.com_matmul import com_matmul_padded

    # MXU-aligned 128 blocks on real TPUs; interpret mode unrolls the
    # grid into the jitted graph, so bigger blocks (fewer, larger
    # dots) are what make the CPU CI path fast — 512³ blocks run a
    # B=32 VGG-11 chain faster than the batched NumPy oracle.
    default_block = 512 if interpret else 128
    bm, bn, bk = (b if b is not None else default_block
                  for b in (block_m, block_n, block_k))
    layer_programs = program.layer_programs

    def matmul(x2d, w2d):
        # one COM kernel call per layer matmul: the K-grid walks the
        # C-block chain, partial sums riding the f32 VMEM scratch;
        # the ReLU epilogue fuses into the last K step (M-type ACT)
        return com_matmul_padded(
            x2d, w2d, activation="relu",
            block_m=bm, block_n=bn, block_k=bk, interpret=interpret,
        )

    def forward(x, ws):
        for lp, w in zip(layer_programs, ws):
            l = lp.layer
            if isinstance(l, ConvSpec):
                K, P, S = l.k, l.padding, l.stride
                Ho, Wo = l.h_out, l.w_out
                xp = jnp.pad(x, ((0, 0), (P, P), (P, P), (0, 0)))
                cols = [
                    xp[:, kr:kr + (Ho - 1) * S + 1:S,
                       kc:kc + (Wo - 1) * S + 1:S, :]
                    for kr in range(K) for kc in range(K)
                ]
                # im2col in (kr, kc, c) order == w.reshape row-major
                patches = jnp.concatenate(cols, axis=-1)
                B = x.shape[0]
                y = matmul(
                    patches.reshape(B * Ho * Wo, K * K * l.c_in),
                    w.reshape(K * K * l.c_in, l.c_out),
                ).reshape(B, Ho, Wo, l.c_out)
                if l.pool_k > 0:
                    y = jax.lax.reduce_window(
                        y, -jnp.inf, jax.lax.max,
                        (1, l.pool_k, l.pool_k, 1),
                        (1, l.pool_stride, l.pool_stride, 1), "VALID",
                    )
                x = y
            else:
                if x.ndim > 2:
                    x = x.reshape(x.shape[0], -1)
                x = matmul(x, w)
        return x

    return forward


@dataclass(frozen=True)
class ExecutionResult:
    """One batched program run: outputs + per-image events + timing."""

    outputs: np.ndarray          # (B, c_out_last) logits (post-activation)
    events: Mapping[str, int]    # per-image totals == network_event_totals
    backend: str
    batch: int
    wall_s: float
    n_shards: int = 1            # devices the batch axis was sharded over

    @property
    def images_s(self) -> float:
        return self.batch / max(self.wall_s, 1e-12)


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
        for lp, w in zip(self.program.layer_programs, self.weights):
            l = lp.layer
            if isinstance(l, ConvSpec):
                x = run_conv_block_chain(lp, w, x)
                if l.pool_k > 0:
                    x = _maxpool_np(x, l.pool_k, l.pool_stride)
            else:
                if x.ndim > 2:
                    x = x.reshape(x.shape[0], -1)  # conv→flatten→FC
                x = run_fc_block_chain(lp, w, x)
        return x

    # ---- jax backend: block chains lowered to the Pallas COM kernel ----
    def _build_jax(self):
        """``(jit_forward, ws, place)``: the jitted chain, its float32
        weights on the device, and the map from a float32 batch to the
        chain's input (padded and sharded in sharded mode)."""
        import jax
        import jax.numpy as jnp

        interpret = self.interpret
        if interpret is None:
            interpret = default_interpret()
        bm, bn, bk = self.blocks
        forward = jax_forward(self.program, interpret=interpret,
                              block_m=bm, block_n=bn, block_k=bk)
        ws = [jnp.asarray(w, dtype=jnp.float32) for w in self.weights]
        if self._mesh is None:
            return jax.jit(forward), ws, lambda x: x

        # sharded mode: the whole layer chain runs per batch shard inside
        # shard_map; logits gather on the ("data",) axis at the end. The
        # chain has no cross-image math, so per-image results are bitwise
        # those of the unsharded path.
        from jax.sharding import PartitionSpec as P

        from repro.parallel.sharding import leading_axis_sharding

        mesh = self._mesh
        n_dev = mesh.shape["data"]
        jit_forward = jax.jit(jax.shard_map(
            forward, mesh=mesh, in_specs=(P("data"), P()),
            out_specs=P("data"), check_vma=False,
        ))
        in_sharding = leading_axis_sharding(mesh, len(self.input_shape) + 1)

        def place(x):
            pad = (-x.shape[0]) % n_dev
            if pad:  # B need not divide the mesh: pad rows are sliced off
                x = jnp.concatenate(
                    [x, jnp.zeros((pad,) + x.shape[1:], x.dtype)])
            return jax.device_put(x, in_sharding)

        return jit_forward, ws, place

    def _jax_args(self, x: np.ndarray):
        import jax.numpy as jnp

        if self._jax_chain is None:
            self._jax_chain = self._build_jax()
        jit_forward, ws, place = self._jax_chain
        return jit_forward, (place(jnp.asarray(x, dtype=jnp.float32)), ws)

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
        """Execute the whole program on a batch of images → logits."""
        x = self._batch(images)
        t0 = time.perf_counter()
        if self.backend == "numpy":
            out = self._run_numpy(x)
        else:
            jit_forward, args = self._jax_args(x)
            out = np.asarray(jit_forward(*args))[:x.shape[0]]
        wall = time.perf_counter() - t0
        return ExecutionResult(
            outputs=out, events=self.events, backend=self.backend,
            batch=x.shape[0], wall_s=wall, n_shards=self.n_shards,
        )

    def __call__(self, images) -> np.ndarray:
        return self.run(images).outputs
