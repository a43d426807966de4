"""The knee of a pool cell's request mix, on the chip: one pool, one
window per offered rate, in one process.

    python3 chipbench/tools/knee_sweep.py --workload pool2.steady \
        --seconds 20 --rates 6 8 10 12 14

For each rate it prints the mean wait before ``execute`` over each
third of the window, with the tails, attainment and member split.  The knee is the
highest rate whose wait does not grow from the first third to the last.
"""
import argparse
import json
import os
import sys

HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path[:0] = [os.path.dirname(HERE), os.path.join(os.path.dirname(HERE), "src")]

import numpy as np  # noqa: E402

from chipbench import traffic as traffic_gen  # noqa: E402
from chipbench.bench import (HERE as BENCH, Spans, cell_files,  # noqa: E402
                             enable_compile_cache, load_json, load_module,
                             require_chip, ROOT)


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, default=20.0)
    ap.add_argument("--seed", type=int, default=20261016)
    ap.add_argument("--rates", type=float, nargs="+", required=True)
    args = ap.parse_args(argv)
    bench = load_json(ROOT, "BENCHMARK.json")
    wl, config, traffic = cell_files(bench, args.workload)
    require_chip(wl["chips"])
    enable_compile_cache()
    driver = load_module(os.path.join(BENCH, "drivers", "pool.py"))
    run = None
    for rate in args.rates:
        traffic["arrivals"]["rate_rps"] = rate
        fresh = driver.Run(config, traffic, args.seed, args.seconds, Spans())
        if run is None:
            run = fresh
            run.setup()
        else:
            run.req, run.tokens = fresh.req, fresh.tokens
            run.attempted, run.captured = fresh.attempted, {}
            run.ex.network = traffic_gen.ScheduledUplink(fresh.req["uplink_ms"])
        run.calls = []
        run.window()
        r = run.requests
        n = len(r["queue_wait_ms"])
        thirds = [float(np.mean(part)) for part in
                  np.array_split(r["queue_wait_ms"], 3)]
        members = np.bincount(r["member"][r["served"]], minlength=2)
        print(json.dumps({
            "rate_rps": rate, "requests": n, "window_s": run.window_s,
            "queue_wait_ms_by_third": thirds,
            "queue_wait_p95_ms": float(np.percentile(r["queue_wait_ms"], 95)),
            "e2e_p95_ms": float(np.percentile(r["e2e_ms"], 95)),
            "sla_attainment": float(r["met"].mean()),
            "per_member": members.tolist(),
            "lateness_p95_ms": float(np.percentile(r["lateness_ms"], 95))
            if len(r["lateness_ms"]) else None}), flush=True)


if __name__ == "__main__":
    main()
