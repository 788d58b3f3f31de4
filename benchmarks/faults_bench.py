"""Fault-injection resilience benchmark: yield / energy / latency vs rate.

Sweeps seeded :class:`repro.faults.FaultSet` injections over fault rates
{0, 1%, 5%, 10%} through all three layers the faults package touches and
emits the resilience-curve artifact CI gates against a committed baseline:

* **compile** — N seeded fault sets per rate on a bounded chip fleet;
  *yield* is the fraction that still compile (``compile_program`` degrades
  the placement around dead tiles/links/chips or raises
  ``FaultCapacityError``). Nested-monotone sampling makes the curve
  monotone non-increasing by construction — gated as
  ``compile.monotone_yield``. Successful compiles also record the
  degradation *price*: extra chips vs the pristine placement and the
  off-chip transfer energy per image (the closed-form the cost model
  charges for every new chip crossing).
* **executor** — seeded weight-cell faults (stuck-at-0/1, sign flips) on
  the VGG-11 oracle, replicating ``executor_bench``'s exact input recipe
  so the 0-rate point reproduces the committed ``logits_checksum``
  bitwise (``executor.zero_matches_executor_baseline``). Faults realize
  once on the resolved float64 weights both backends consume, so the
  numpy oracle and the Pallas ``com_matmul`` path see *bitwise identical*
  faulted weights — gated as ``executor.backends_fault_mask_identical``.
* **serve** — transient slot faults through the continuous-batching
  engine with retry-and-re-prefill recovery
  (:class:`repro.runtime.fault_tolerance.RestartPolicy`). The 0-rate
  point reproduces the committed ``serve-bench`` counters exactly, and
  every faulted run must still emit token-identical output
  (``serve.tokens_identical.*``) — faults cost *ticks* (backoff +
  retries, the latency curve), never tokens.

    source scripts/bench_env.sh
    PYTHONPATH=src python benchmarks/faults_bench.py --out faults-bench.json
"""
from __future__ import annotations

import argparse
import json
import sys
import time

import numpy as np

RATES = (0.0, 0.01, 0.05, 0.10)
# rate-keyed dict keys must not contain "." (compare_bench metric paths
# split on dots): 0.05 -> "r5"
RATE_KEYS = {0.0: "r0", 0.01: "r1", 0.05: "r5", 0.10: "r10"}

# committed 0-rate anchors (benchmarks/baselines/): the no-fault points of
# the resilience curves must reproduce these exactly
EXECUTOR_BASELINE_CHECKSUM = 117.57582911326853
SERVE_BASELINE = dict(generated_tokens=512, decode_steps=124, occupancy=4.0)


def _rate_dict() -> dict:
    return {RATE_KEYS[r]: None for r in RATES}


def bench_compile(network: str, n_seeds: int, spare_chips: int) -> dict:
    """Yield + degradation price of fault-aware compilation per rate."""
    from repro.core.program import compile_program
    from repro.faults import FaultCapacityError, FaultSet
    from repro.sweep.registry import resolve_network

    wl = resolve_network(network)
    pristine = compile_program(wl)
    arch = pristine.arch
    pristine_chips = max(c for a in pristine.allocs for c in a.chip_ids) + 1
    fleet = pristine_chips + spare_chips

    def offchip_j(allocs) -> float:
        from repro.core.simulator import offchip_values_img

        return (offchip_values_img(allocs) * arch.precision_bits
                * arch.energy.interchip_pj_per_bit * arch.energy_scale()
                * 1e-12)

    out = dict(network=network, n_seeds=n_seeds, fleet_chips=fleet,
               pristine_chips=pristine_chips,
               pristine_offchip_energy_img_j=offchip_j(pristine.allocs),
               yield_by_rate=_rate_dict(), mean_extra_chips=_rate_dict(),
               mean_offchip_energy_img_j=_rate_dict())
    yields = []
    for rate in RATES:
        ok, chips, energies = 0, [], []
        for seed in range(n_seeds):
            fs = FaultSet.sample(rate, seed, arch=arch, n_chips=fleet)
            try:
                prog = compile_program(wl, faults=fs)
            except FaultCapacityError:
                continue
            ok += 1
            chips.append(max(c for a in prog.allocs for c in a.chip_ids) + 1)
            energies.append(offchip_j(prog.allocs))
        key = RATE_KEYS[rate]
        out["yield_by_rate"][key] = ok / n_seeds
        out["mean_extra_chips"][key] = (
            sum(chips) / ok - pristine_chips if ok else None)
        out["mean_offchip_energy_img_j"][key] = (
            sum(energies) / ok if ok else None)
        yields.append(ok / n_seeds)
    out["monotone_yield"] = all(
        a >= b for a, b in zip(yields, yields[1:]))
    return out


def bench_executor(network: str, batch: int, seed: int,
                   run_jax: bool) -> dict:
    """Weight-fault accuracy curve; backends see identical fault masks."""
    from repro.core.executor import ProgramExecutor, random_weights
    from repro.core.program import compile_program
    from repro.faults import FaultSet
    from repro.sweep.registry import resolve_network

    wl = resolve_network(network)
    program = compile_program(wl)
    weights = random_weights(program, seed=seed)
    # replicate executor_bench's exact draw order (batches [1, batch]) so
    # the 0-rate checksum reproduces the committed baseline bitwise
    rng = np.random.default_rng(seed + 1)
    oracle = ProgramExecutor(program, weights, backend="numpy")
    rng.normal(size=(1,) + oracle.input_shape)
    imgs = rng.normal(size=(batch,) + oracle.input_shape)
    clean = oracle.run(imgs)
    checksum = float(np.abs(clean.outputs).sum())
    clean_argmax = np.argmax(clean.outputs, axis=-1)

    interpret = None
    if run_jax:
        from repro.core.executor import default_interpret
        from repro.launch.compile_cache import init_compile_cache

        interpret = default_interpret()
        init_compile_cache()

    out = dict(network=network, batch=batch,
               logits_checksum_r0=checksum,
               zero_matches_executor_baseline=bool(
                   abs(checksum - EXECUTOR_BASELINE_CHECKSUM)
                   <= 1e-9 * EXECUTOR_BASELINE_CHECKSUM),
               backends_fault_mask_identical=True,
               mask_checksum=_rate_dict(), n_cells=_rate_dict(),
               logits_l1_delta=_rate_dict(), argmax_delta_frac=_rate_dict(),
               jax_argmax_agree_frac=_rate_dict())
    for rate in RATES:
        key = RATE_KEYS[rate]
        fs = FaultSet(cell_rate=rate, cell_seed=seed)
        ex = ProgramExecutor(program, weights, backend="numpy", faults=fs)
        info = ex.fault_info or dict(n_cells=0, mask_checksum=0.0)
        got = ex.run(imgs)
        out["mask_checksum"][key] = info["mask_checksum"]
        out["n_cells"][key] = info["n_cells"]
        out["logits_l1_delta"][key] = float(
            np.abs(got.outputs - clean.outputs).sum())
        out["argmax_delta_frac"][key] = float(
            (np.argmax(got.outputs, axis=-1) != clean_argmax).mean())
        if run_jax:
            jx = ProgramExecutor(program, weights, backend="jax",
                                 interpret=interpret, faults=fs)
            # THE cross-backend contract: both executors resolved the same
            # faulted weight arrays, byte for byte
            if ex.weights is not None:
                same = all(
                    np.array_equal(a, b)
                    for a, b in zip(ex.weights, jx.weights))
                out["backends_fault_mask_identical"] &= same
            jout = jx.run(imgs)
            out["jax_argmax_agree_frac"][key] = float(
                (np.argmax(jout.outputs, axis=-1)
                 == np.argmax(got.outputs, axis=-1)).mean())
    return out


def bench_serve(arch: str, batch: int, n_requests: int, prompt_len: int,
                max_new: int, seed: int) -> dict:
    """Transient-fault latency curve with token-identical recovery."""
    import jax

    from repro.configs import get_config
    from repro.faults import TransientFaults
    from repro.models.transformer import CallConfig, build_model
    from repro.runtime.fault_tolerance import RestartPolicy
    from repro.serve.admission import AdmissionQueue
    from repro.serve.engine import Engine

    sys.path.insert(0, "benchmarks")
    from serve_bench import make_requests

    cfg = get_config(arch).reduced()
    model = build_model(cfg, CallConfig(remat="none"))
    params = model.init(jax.random.PRNGKey(0))
    max_seq = prompt_len + max_new
    eng = Engine(model, params, batch=batch, max_seq=max_seq)

    wave = lambda: make_requests(n_requests, prompt_len, max_new, 0.0,
                                 cfg.vocab_size, seed=seed)

    # 0-rate anchor: the legacy batch path, matching serve-bench exactly
    clean = eng.generate(wave(), seed=seed)
    s0 = eng.last_stats
    clean_toks = [r.out_tokens for r in clean]
    zero_ok = all(
        abs(s0[k] - SERVE_BASELINE[k]) <= 1e-9 for k in SERVE_BASELINE)

    out = dict(arch=arch, batch=batch, n_requests=n_requests,
               prompt_len=prompt_len, max_new_tokens=max_new, seed=seed,
               zero_matches_serve_baseline=bool(zero_ok),
               generated_tokens_r0=s0["generated_tokens"],
               decode_steps_r0=s0["decode_steps"],
               occupancy_r0=s0["occupancy"],
               completed=_rate_dict(), faults_injected=_rate_dict(),
               retries=_rate_dict(), makespan_ticks=_rate_dict(),
               latency_p50_ticks=_rate_dict(), latency_p99_ticks=_rate_dict(),
               tokens_identical=_rate_dict())
    for rate in RATES:
        key = RATE_KEYS[rate]
        reqs = wave()
        queue = AdmissionQueue.from_requests(reqs, max_seq=max_seq)
        policy = RestartPolicy(max_restarts=10_000_000, backoff_s=1.0,
                               backoff_mult=1.0)
        done = eng.serve(queue, seed=seed, do_sample=False,
                         faults=TransientFaults(slot_rate=rate, seed=seed),
                         restart_policy=policy, backoff_cap=4.0)
        st = eng.last_stats
        lat = np.array([r.finish_time - r.arrival_time for r in done])
        out["completed"][key] = len(done)
        out["faults_injected"][key] = st["faults_injected"]
        out["retries"][key] = st["retries"]
        out["makespan_ticks"][key] = st["makespan_ticks"]
        out["latency_p50_ticks"][key] = float(np.percentile(lat, 50))
        out["latency_p99_ticks"][key] = float(np.percentile(lat, 99))
        out["tokens_identical"][key] = bool(
            [r.out_tokens for r in reqs] == clean_toks)
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--network", default="vgg11-cifar")
    ap.add_argument("--serve-arch", default="smollm-135m")
    ap.add_argument("--seeds", type=int, default=8,
                    help="fault-set samples per rate in the compile sweep")
    ap.add_argument("--spare-chips", type=int, default=6,
                    help="fleet headroom beyond the pristine placement")
    ap.add_argument("--batch", type=int, default=8,
                    help="executor image batch (must match the committed "
                         "executor baseline's checksum batch)")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--no-jax", action="store_true",
                    help="skip the Pallas-path cross-check runs")
    ap.add_argument("--out", default=None)
    args = ap.parse_args(argv)

    t0 = time.perf_counter()
    payload = dict(
        schema_version=1,
        fault_rates=list(RATES),
        compile=bench_compile(args.network, args.seeds, args.spare_chips),
        executor=bench_executor(args.network, args.batch, args.seed,
                                run_jax=not args.no_jax),
        serve=bench_serve(args.serve_arch, 4, 16, 8, 32, args.seed),
    )
    payload["wall_s"] = time.perf_counter() - t0

    text = json.dumps(payload, indent=2)
    if args.out:
        with open(args.out, "w") as f:
            f.write(text + "\n")
        print(f"wrote {args.out}")
    else:
        print(text)
    ok = (payload["compile"]["monotone_yield"]
          and payload["executor"]["zero_matches_executor_baseline"]
          and payload["executor"]["backends_fault_mask_identical"]
          and payload["serve"]["zero_matches_serve_baseline"]
          and all(payload["serve"]["tokens_identical"].values()))
    print(f"resilience gates: {'PASS' if ok else 'FAIL'}", file=sys.stderr)
    return 0 if ok else 1


if __name__ == "__main__":
    raise SystemExit(main())
