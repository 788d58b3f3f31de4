"""Run one cell of the benchmark on the chips of this machine.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

``<cell>`` is a ``workloads`` entry of ``BENCHMARK.json``. The run exits
non-zero, before any work, when JAX's first device is not a TPU or there
are fewer devices than the cell asks for. It sets the cell up from the
seed (``setup_s`` runs from the start of this process to the first timed
call), measures a closed loop for ``--seconds``, frees the program, checks
the output of every timed call against the plain reference, and prints as
its last line of standard output one JSON object: ``correct``,
``attempted``, ``failed``, ``metrics`` (the cell's end-to-end metrics, or
with ``--trace 1`` its per-layer metrics), ``device``, with ``--trace 1``
``breakdown``, and last ``checks``, each number compared beside its limit.
The same comparisons are the last lines of standard error.
"""
import time

T0 = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parents[1]
# the checkout, not bench/, heads the path: bench's modules import as
# bench.*, and bench/trace.py does not shadow the standard library's trace
sys.path[0] = str(ROOT)
sys.path.insert(1, str(ROOT / "src"))
# the TPU library otherwise writes its logs to /tmp/tpu_logs, outside the
# checkout and whatever TMPDIR says
os.environ.setdefault("TPU_LOG_DIR", "disabled")


def parse(argv):
    p = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    p.add_argument("--workload", required=True)
    p.add_argument("--seed", type=int, required=True)
    p.add_argument("--seconds", type=float, required=True)
    p.add_argument("--trace", type=int, choices=(0, 1), default=0)
    return p.parse_args(argv)


def err(line: str) -> None:
    print(line, file=sys.stderr, flush=True)


def main(argv=None) -> int:
    args = parse(argv)
    if args.seed < 0:
        err("run.py: --seed must be a whole number >= 0")
        return 2
    import jax

    devices = jax.devices()
    if devices[0].platform != "tpu":
        err(f"run.py: JAX's first device is {devices[0].platform!r}, not "
            "a TPU; nothing was run")
        return 2
    from bench import harness

    spec = harness.cell_spec(args.workload)
    if len(devices) < spec["chips"]:
        err(f"run.py: {args.workload} needs {spec['chips']} chips, JAX "
            f"finds {len(devices)}; nothing was run")
        return 2
    harness.configure_cache()
    result = harness.run(spec, args.seed, args.seconds, bool(args.trace), T0,
                         say=err)
    err(f"correct: {result['correct']}")
    for name, c in result["checks"].items():
        err(f"check {name}: {c['value']!r} (limit {c['limit']!r})")
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
