"""Whole-program executor benchmark: the image→logits perf artifact.

Runs :class:`repro.core.executor.ProgramExecutor` over a compiled network
(default VGG-11/CIFAR) at several batch sizes and times three paths:

* ``numpy`` — the batched block-semantics oracle (one call, B images);
* ``numpy_per_image`` — the same oracle driven one image at a time (the
  old per-layer/per-image loop the batched executor replaces);
* ``jax`` — every block einsum lowered to the Pallas ``com_matmul``
  kernel, whole chain jitted; ``interpret=True`` off-TPU so CPU CI
  exercises the real kernel path (noted in the artifact — on-device
  numbers are the headline, interpret numbers are the CI proxy);
* ``jax-sharded`` (``--shard auto``) — the same jitted chain with the
  image-batch axis partitioned over a ``("data",)`` device mesh
  (``ProgramExecutor(..., shard="auto")``); logits are checked bitwise
  against the unsharded jax run (``sharded_matches_jax``) and the device
  count / shard count land in the artifact.

Cross-checks ride along: jax-vs-numpy output agreement (float32 kernel vs
float64 oracle) and the per-image event totals against the
``network_event_totals`` closed forms. Emits machine-readable JSON.

    PYTHONPATH=src python benchmarks/executor_bench.py --out executor-bench.json
    PYTHONPATH=src python benchmarks/executor_bench.py --batches 1 8 32 --repeats 3
"""
from __future__ import annotations

import argparse
import json
import sys
import time

import numpy as np

from repro.core.executor import ProgramExecutor, random_weights
from repro.core.program import compile_program
from repro.core.simulator import EVENT_FIELDS, network_event_totals
from repro.sweep.registry import resolve_network

DEFAULT_BATCHES = (1, 8, 32)


def _best_of(fn, repeats: int) -> float:
    best = None
    for _ in range(max(repeats, 1)):
        t0 = time.perf_counter()
        fn()
        dt = time.perf_counter() - t0
        best = dt if best is None else min(best, dt)
    return best


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--network", default="vgg11-cifar",
                    help="network name (default: vgg11-cifar)")
    ap.add_argument("--batches", nargs="*", type=int,
                    default=list(DEFAULT_BATCHES),
                    help=f"batch sizes (default: {list(DEFAULT_BATCHES)})")
    ap.add_argument("--backends", nargs="*", default=["numpy", "jax"],
                    choices=("numpy", "jax"), help="backends to time")
    ap.add_argument("--shard", choices=("off", "auto"), default="off",
                    help="'auto': additionally time the mesh-sharded jax "
                         "executor (image-batch axis over a ('data',) "
                         "mesh) and check its logits bitwise against the "
                         "unsharded jax run; falls back to unsharded on a "
                         "single device")
    ap.add_argument("--repeats", type=int, default=2,
                    help="timing repetitions (best-of; first jax run warms "
                         "the jit outside the timed region)")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--out", default=None,
                    help="write JSON here (default: stdout)")
    args = ap.parse_args(argv)

    wl = resolve_network(args.network)
    program = compile_program(wl)
    weights = random_weights(program, seed=args.seed)
    rng = np.random.default_rng(args.seed + 1)

    oracle = ProgramExecutor(program, weights, backend="numpy")
    totals = network_event_totals(wl.layers, program.arch)
    events_match = all(oracle.events[f] == totals[f] for f in EVENT_FIELDS)

    interpret = None
    if "jax" in args.backends:
        from repro.core.executor import default_interpret
        from repro.launch.compile_cache import init_compile_cache

        interpret = default_interpret()
        init_compile_cache()

    shard = args.shard == "auto" and "jax" in args.backends
    batches = {}
    worst_rel_err = 0.0
    sharded_matches = True
    n_shards = 1
    logits_checksum = None
    for b in args.batches:
        imgs = rng.normal(size=(b,) + oracle.input_shape)
        row = {}
        if "numpy" in args.backends:
            ref = oracle.run(imgs)
            if b == max(args.batches):
                # deterministic fidelity fingerprint of the oracle logits
                # (float64 sums vary only ~1e-13 rel across BLAS builds,
                # far under the 1e-9 compare_bench fidelity gate)
                logits_checksum = float(np.abs(ref.outputs).sum())
            wall = _best_of(lambda: oracle.run(imgs), args.repeats)
            row["numpy_wall_s"] = wall
            row["numpy_img_s"] = b / wall

            def per_image():
                for i in range(b):
                    oracle.run(imgs[i])
            wall = _best_of(per_image, args.repeats)
            row["numpy_per_image_wall_s"] = wall
            row["numpy_per_image_img_s"] = b / wall
        if "jax" in args.backends:
            jx = ProgramExecutor(program, weights, backend="jax",
                                 interpret=interpret)
            got = jx.run(imgs)  # warm the jit outside the timed region
            wall = _best_of(lambda: jx.run(imgs), args.repeats)
            row["jax_wall_s"] = wall
            row["jax_img_s"] = b / wall
            if "numpy" in args.backends:
                scale = max(float(np.abs(ref.outputs).max()), 1e-30)
                err = float(np.abs(got.outputs - ref.outputs).max()) / scale
                worst_rel_err = max(worst_rel_err, err)
                row["jax_vs_per_image_speedup"] = (
                    row["numpy_per_image_wall_s"] / max(wall, 1e-12))
                row["jax_vs_numpy_speedup"] = (
                    row["numpy_wall_s"] / max(wall, 1e-12))
            if shard:
                jsh = ProgramExecutor(program, weights, backend="jax",
                                      interpret=interpret, shard="auto")
                n_shards = jsh.n_shards
                got_sh = jsh.run(imgs)  # warm the jit outside timing
                wall = _best_of(lambda: jsh.run(imgs), args.repeats)
                row["jax_sharded_wall_s"] = wall
                row["jax_sharded_img_s"] = b / wall
                # sharding splits the batch axis only — no cross-image
                # math — so logits must match the unsharded jax run bitwise
                sharded_matches &= bool(np.array_equal(
                    np.asarray(got_sh.outputs), np.asarray(got.outputs)))
        batches[str(b)] = row

    payload = dict(
        network=args.network,
        n_layers=len(wl),
        batches=batches,
        backends=list(args.backends),
        interpret=interpret,
        events_match=events_match,
        events={f: int(totals[f]) for f in EVENT_FIELDS},
        note=(
            "numpy oracle only; the Pallas kernel path was not run."
            if interpret is None else
            "interpret=True: the Pallas com_matmul kernel ran in interpret "
            "mode (no TPU in this environment); kernel-path numbers are a "
            "CPU CI proxy, on-device numbers are the headline."
            if interpret else
            "compiled kernel path (on-device)."
        ),
    )
    if "jax" in args.backends and "numpy" in args.backends:
        payload["jax_max_rel_err_vs_numpy"] = worst_rel_err
    if logits_checksum is not None:
        payload["logits_checksum"] = logits_checksum
        payload["logits_checksum_batch"] = max(args.batches)
    if "jax" in args.backends:
        import jax

        payload["n_devices"] = len(jax.devices())
    if shard:
        payload["n_shards"] = n_shards
        payload["sharded_matches_jax"] = sharded_matches

    top = str(max(args.batches)) if args.batches else None
    head = [f"{args.network}: events_match={events_match}"]
    if top and "numpy" in args.backends:
        head.append(
            f"B={top}: numpy {batches[top]['numpy_img_s']:.1f} img/s "
            f"(per-image loop {batches[top]['numpy_per_image_img_s']:.1f})")
    if top and "jax" in args.backends and "jax_img_s" in batches[top]:
        head.append(
            f"jax {batches[top]['jax_img_s']:.1f} img/s"
            + (f" ({batches[top]['jax_vs_per_image_speedup']:.2f}x vs "
               f"per-image loop)" if "jax_vs_per_image_speedup" in batches[top]
               else "")
            + (" [interpret]" if interpret else ""))
    if top and shard and "jax_sharded_img_s" in batches[top]:
        head.append(
            f"jax-sharded {batches[top]['jax_sharded_img_s']:.1f} img/s "
            f"({n_shards} shards, bitwise=={sharded_matches})")
    print("; ".join(head), file=sys.stderr)

    text = json.dumps(payload, indent=2)
    if args.out:
        with open(args.out, "w") as f:
            f.write(text + "\n")
        print(f"wrote {args.out}", file=sys.stderr)
    else:
        print(text)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
