"""Readings that the output check's limit is set from, on the chip.

    python3 bench/control.py --workload <cell> --seeds 11 12 ... [--seconds 2]

For each seed, in this one process: the cell is set up as a run sets it
up, driven through a short window at its own load, freed, and its outputs
compared with the reference: the program's reading. Then the control, the
reference at the next precision below (three bfloat16 passes), is put in
the program's place on the same inputs and weights: the control's
reading. The limit has to lie above every program reading and below every
control reading. Prints one JSON object per seed and a summary line.
Benchmark runs never run this.
"""
import time

T0 = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parents[1]
sys.path[0] = str(ROOT)
sys.path.insert(1, str(ROOT / "src"))


def readings(workload: str, seeds, seconds: float, *, interpret=False,
             mix=None):
    from bench import harness

    spec = harness.cell_spec(workload)
    out = []
    for seed in seeds:
        cfg, mix_, cell = harness.setup_cell(spec, seed, interpret=interpret,
                                             mix=mix)
        win = harness.measure(cell.call, seconds)
        cell.close()
        limit = cfg["check"]["max_rel_err"]
        program = cell.check(win.outputs, limit)
        control = cell.control(limit)
        out.append({
            "seed": seed, "calls": len(win.outputs),
            "program": program["checks"]["max_rel_err"]["value"],
            "control": control["checks"]["max_rel_err"]["value"],
            "limit": limit, "program_correct": program["correct"],
            "control_correct": control["correct"],
        })
    return out


def main(argv=None) -> int:
    p = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    p.add_argument("--workload", required=True)
    p.add_argument("--seeds", type=int, nargs="+", required=True)
    p.add_argument("--seconds", type=float, default=2.0)
    args = p.parse_args(argv)
    import jax

    if jax.devices()[0].platform != "tpu":
        print("control.py: JAX's first device is not a TPU",
              file=sys.stderr)
        return 2
    from bench import harness

    harness.configure_cache()
    rows = readings(args.workload, args.seeds, args.seconds)
    for r in rows:
        print(json.dumps(r), flush=True)
    lower = max(r["program"] for r in rows)
    upper = min(r["control"] for r in rows)
    print(json.dumps({"workload": args.workload, "lower": lower,
                      "upper": upper, "ratio": upper / lower,
                      "device": jax.devices()[0].device_kind}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
