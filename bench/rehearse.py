"""Rehearse a cell on the CPU at a tiny size, before spending chip time.

    JAX_PLATFORMS=cpu python3 bench/rehearse.py --workload <cell> [--batch 2] [--trace 1]

Drives the whole of a run, as ``bench/run.py`` does, with the Pallas
kernels in interpret mode and the mix cut to ``--batch`` inputs a call and
two distinct batches: set-up from the seed, the closed-loop window, the
output check against the reference and, with ``--trace 1``, the trace's
reduction. It prints the check and which per-layer metrics found something
to read, and never a metrics line: nothing measured on the CPU is a
device metric.
"""
import os
import time

T0 = time.perf_counter()

import argparse  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parents[1]
sys.path[0] = str(ROOT)
sys.path.insert(1, str(ROOT / "src"))
os.environ.setdefault("JAX_PLATFORMS", "cpu")

# peaks of the chip the cells are written for, so that the readers run
REHEARSAL_KIND = "TPU v5 lite"


def main(argv=None) -> int:
    p = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    p.add_argument("--workload", required=True)
    p.add_argument("--seed", type=int, default=2**31 + 11)
    p.add_argument("--batch", type=int, default=2)
    p.add_argument("--seconds", type=float, default=1.0)
    p.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = p.parse_args(argv)

    from bench import harness, traffic

    spec = harness.cell_spec(args.workload)
    mix = dict(traffic.load(spec["traffic"]), batch=args.batch,
               distinct_batches=2)
    result = harness.run(spec, args.seed, args.seconds, bool(args.trace), T0,
                         interpret=True, mix=mix, peaks_kind=REHEARSAL_KIND,
                         say=lambda line: None)
    print(f"rehearsal of {args.workload} at batch {args.batch} on "
          f"{result['device']['platform']}: correct={result['correct']} "
          f"attempted={result['attempted']} failed={result['failed']}")
    for name, c in result["checks"].items():
        print(f"check {name}: {c['value']!r} (limit {c['limit']!r})")
    if args.trace:
        print("per-layer metrics that found something to read:",
              sorted(result["metrics"]) or "none")
    return 0 if result["correct"] else 1


if __name__ == "__main__":
    sys.exit(main())
