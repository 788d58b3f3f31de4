"""Run a cell several times and report how widely its metrics spread.

    python3 bench/spread.py --workload <cell> --seeds 1 2 3 4 5 6 --sets 2 \
        [--seconds 20] [--trace-seeds 7 8 9] [--out spread.jsonl]

Runs ``bench/run.py`` once per seed in each set, one process after
another (this process never touches JAX, so each run has the chip to
itself), the same seeds in every set. For each set and end-to-end metric
it prints the median and the spread: the distance between the first and
third quartile (``statistics.quantiles(values, n=4)``) over the median.
``--trace-seeds`` adds one traced run per seed after the sets. Every
run's last line goes to ``--out``.
"""
import argparse
import json
import statistics
import subprocess
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]


def quartile_spread(values):
    q1, q2, q3 = statistics.quantiles(values, n=4)
    return (q3 - q1) / q2


def one(workload, seed, seconds, trace):
    cmd = [sys.executable, str(ROOT / "bench" / "run.py"), "--workload",
           workload, "--seed", str(seed), "--seconds", str(seconds),
           "--trace", str(trace)]
    p = subprocess.run(cmd, cwd=ROOT, capture_output=True, text=True)
    lines = p.stdout.strip().splitlines()
    if p.returncode != 0 or not lines:
        return {"seed": seed, "trace": trace, "rc": p.returncode,
                "stderr_end": p.stderr[-2000:]}
    return dict(json.loads(lines[-1]), seed=seed, trace=trace, rc=0,
                stderr_end=p.stderr.strip().splitlines()[-3:])


def main(argv=None) -> int:
    p = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    p.add_argument("--workload", required=True)
    p.add_argument("--seeds", type=int, nargs="+", required=True)
    p.add_argument("--sets", type=int, default=2)
    p.add_argument("--seconds", type=float, default=20)
    p.add_argument("--trace-seeds", type=int, nargs="*", default=[])
    p.add_argument("--out", type=Path)
    args = p.parse_args(argv)

    sink = args.out.open("a") if args.out else None
    sets = []
    for k in range(args.sets):
        rows = []
        for seed in args.seeds:
            r = one(args.workload, seed, args.seconds, 0)
            r["set"] = k
            rows.append(r)
            print(json.dumps(r), flush=True)
            if sink:
                sink.write(json.dumps(r) + "\n")
                sink.flush()
        sets.append(rows)
    for seed in args.trace_seeds:
        r = one(args.workload, seed, args.seconds, 1)
        print(json.dumps(r), flush=True)
        if sink:
            sink.write(json.dumps(r) + "\n")
    if sink:
        sink.close()

    summary = {"workload": args.workload, "sets": []}
    for rows in sets:
        ok = [r for r in rows if r["rc"] == 0]
        entry = {"runs": len(rows), "ok": len(ok),
                 "correct": sum(1 for r in ok if r["correct"])}
        if len(ok) >= 2:
            for name in ok[0]["metrics"]:
                vals = [r["metrics"][name]["value"] for r in ok]
                entry[name] = {"median": statistics.median(vals),
                               "spread": quartile_spread(vals)}
        summary["sets"].append(entry)
    print(json.dumps(summary), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
