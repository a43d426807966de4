# One function per paper table/figure. Prints ``name,us_per_call,derived`` CSV.
import argparse
import json
import sys
import traceback


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--only", default=None,
                    help="comma-separated benchmark names (default: all)")
    ap.add_argument("--fast", action="store_true",
                    help="skip the live-pool serving benchmark and cap "
                         "policy_throughput at small batches")
    ap.add_argument("--smoke", action="store_true",
                    help="run every registered benchmark at toy scale "
                         "(implies --fast): the CI bit-rot guard — a "
                         "benchmark that stopped importing or running "
                         "fails here instead of at sweep time")
    ap.add_argument("--fail-fast", action="store_true",
                    help="abort on the first failing benchmark")
    ap.add_argument("--json", action="store_true",
                    help="also write BENCH_<name>.json per benchmark "
                         "(perf trajectory record)")
    args = ap.parse_args()
    if args.smoke:
        args.fast = True

    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()

    from benchmarks import drift_resilience as dr
    from benchmarks import elastic_controllers as ec
    from benchmarks import engine_throughput as et
    from benchmarks import fleet_throughput as ft
    from benchmarks import load_sweep as ls
    from benchmarks import paper_figures as pf
    from benchmarks import policy_throughput as pt
    from benchmarks import premodel as pm
    from benchmarks import roofline as rl
    from benchmarks import scenario_suite as sc

    # Toy-scale knobs used under --smoke; full scale otherwise.
    fig_kw = {"n": 60} if args.smoke else {}

    def smoke_load_sweep():
        return (ls.sweep_rows(rates=(5.0, 40.0), n_requests=120)
                + ls.admission_rows(rates=(40.0,), n_requests=120))

    benches = {
        "table2": pf.table2_zoo,
        "fig3": pf.fig3_latency_table,
        "fig5": lambda: pf.fig5_prototype(**fig_kw),
        "fig6": lambda: pf.fig6_vs_static_greedy(**fig_kw),
        "fig7": lambda: pf.fig7_cv_sweep(**fig_kw),
        "fig8": lambda: pf.fig8_usage_vs_cv(**fig_kw),
        "fig9": lambda: pf.fig9_decomposition(**fig_kw),
        "threshold": lambda: pf.threshold_ablation(**fig_kw),
        "roofline_single": lambda: rl.roofline_rows("single"),
        "roofline_multi": lambda: rl.roofline_rows("multi"),
        "kernels": lambda: rl.kernel_micro(
            seq_len=128 if args.smoke else 512),
        "tpu_pool": (lambda: _tpu_pool(n=120, slas=(100, 600)))
        if args.smoke else _tpu_pool,
        "load_sweep": smoke_load_sweep if args.smoke else
        (lambda: ls.sweep_rows() + ls.admission_rows()),
        "sla_frontier": (lambda: ls.frontier_rows(slas=(250.0,), n=2048))
        if args.smoke else ls.frontier_rows,
        "policy_throughput": lambda: pt.bench_rows(fast=args.fast),
        # events/sec + requests/sec at 10k/100k/1M (2k under --smoke)
        "engine_throughput": lambda: et.bench_rows(fast=args.fast),
        # every registered named scenario, end to end (toy scale under
        # --smoke: the registry's bit-rot guard)
        "scenario_suite": (lambda: sc.suite_rows(scale=0.1))
        if args.smoke else sc.suite_rows,
        # drift/fault recovery trajectories; carries the tier-1-visible
        # resilience assertion (adaptive post-drift attainment >= 0.9
        # and >= 2x the frozen-profile ablation)
        "drift_resilience": lambda: dr.bench_rows(fast=args.fast),
        # mid-run elastic controllers vs epoch-boundary autoscaling;
        # carries the tier-1-visible gates (zero in-flight requests
        # lost to drain-based scale-in, and the capped proportional
        # controller beating the epoch baseline's pooled attainment at
        # lower replica-seconds on the 10x load step)
        "elastic_controllers": lambda: ec.bench_rows(fast=args.fast),
        # multi-cell scaling + spill frontier + batch-window ablation;
        # carries the tier-1-visible fleet guard (4-cell toy >= 0.9
        # attainment and >= 2.5x the 1-cell goodput under --smoke)
        "fleet_throughput": lambda: ft.bench_rows(fast=args.fast),
        # conditional-profile + tail-quantile routing; carries the
        # tier-1-visible premodel guards (conditional >= +0.02 accuracy
        # at equal attainment; p95 budgets beat mean budgets on tail
        # attainment)
        "premodel": lambda: pm.bench_rows(fast=args.fast),
    }
    if args.smoke:
        # Toy pool (2 reduced-width variants, short cache, 6 requests):
        # the real-JAX serving path stays under the bit-rot guard too.
        benches["live_pool"] = lambda: _live_pool(
            widths=(0.5, 1.0), cache_len=32, n=6, tokens_shape=(1, 16))
    elif not args.fast:
        benches["live_pool"] = _live_pool

    selected = args.only.split(",") if args.only else list(benches)
    unknown = [n for n in selected if n not in benches]
    if unknown:
        raise SystemExit(f"unknown benchmark(s): {', '.join(unknown)} "
                         f"(available: {', '.join(benches)})")
    print("name,us_per_call,derived")
    failures = 0
    for name in selected:
        try:
            rows = list(benches[name]())
            for row in rows:
                print(f"{row[0]},{row[1]:.3f},{row[2]}")
            if args.json:
                # Toy-scale rows must not clobber the tracked full-scale
                # perf-trajectory records.
                suffix = "_smoke" if args.smoke else ""
                with open(f"BENCH_{name}{suffix}.json", "w") as fh:
                    json.dump({"benchmark": name,
                               "rows": [{"name": r[0], "us_per_call": r[1],
                                         "derived": r[2]} for r in rows]},
                              fh, indent=2)
        except Exception as e:
            failures += 1
            traceback.print_exc(file=sys.stderr)
            print(f"{name},0.0,ERROR:{type(e).__name__}:{e}", file=sys.stderr)
            if args.fail_fast:
                break
    if failures:
        raise SystemExit(1)


def _tpu_pool(n: int = 2000, slas=(100, 300, 600, 1500, 3000)):
    """Beyond-paper: ModiPick over (arch × mesh) TPU pool members whose
    latency profiles come from the dry-run rooflines (core/tpu_pool.py)."""
    import os
    from repro.core.netmodel import NetworkModel
    from repro.core.policy import ModiPick, StaticGreedy
    from repro.core.simulate import Simulator
    from repro.core.tpu_pool import load_pool, to_zoo

    results = "benchmarks/results/dryrun"
    if not os.path.isdir(results) or not load_pool(results):
        results = "benchmarks/results/dryrun_baseline"
    pool = load_pool(results)
    if not pool:
        return [("tpu_pool/skipped", 0.0, "no dry-run artifacts")]
    zoo = to_zoo(pool)
    sim = Simulator(entries=zoo, network=NetworkModel(20.0, 10.0), seed=20)
    rows = []
    for sla in slas:
        mp = sim.run(ModiPick(t_threshold=50.0, gamma=4.0), sla, n)
        sg = sim.run(StaticGreedy(sla), sla, n)
        top = max(mp.model_usage, key=mp.model_usage.get)
        rows.append((f"tpu_pool/sla_{sla}", 0.0,
                     f"mp_attain={mp.sla_attainment:.3f};mp_q={mp.mean_accuracy:.3f};"
                     f"sg_attain={sg.sla_attainment:.3f};sg_q={sg.mean_accuracy:.3f};"
                     f"top={top}"))
    return rows


def _live_pool(widths=(0.5, 1.0, 2.0), cache_len=160, n=60,
               tokens_shape=(4, 128)):
    """Live serving e2e: real JAX pool behind ModiPick vs static greedy."""
    import numpy as np
    from repro.configs.registry import get_config
    from repro.core.netmodel import NetworkModel
    from repro.core.policy import ModiPick, StaticGreedy
    from repro.serving.executor import PoolExecutor
    from repro.serving.pool import scaled_family

    rows = []
    variants = scaled_family(get_config("qwen2-1.5b"), widths=widths,
                             cache_len=cache_len)
    tokens = np.random.default_rng(0).integers(0, 500, tokens_shape,
                                               dtype=np.int32)
    net = NetworkModel(mean_ms=20.0, std_ms=10.0)
    for name, pol in [("modipick", ModiPick(t_threshold=25.0)),
                      ("static_greedy", StaticGreedy(120.0))]:
        ex = PoolExecutor(variants, net, pol, seed=3)
        ex.warm_up(tokens)
        for _ in range(n):
            ex.execute(tokens, t_sla=120.0)
        s = ex.summary()
        rows.append((f"live_pool/{name}", s["mean_latency_ms"] * 1e3,
                     f"attain={s['sla_attainment']:.3f};quality={s['mean_quality']:.3f};"
                     f"p99_ms={s['p99_latency_ms']:.1f}"))
    return rows


if __name__ == '__main__':
    main()
