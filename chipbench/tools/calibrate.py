"""Readings that set a cell's correctness limit, on the chip: for each
seed, the number the cell compares for the program and for its control.

    python3 chipbench/tools/calibrate.py --workload pool2.steady \
        --seconds 8 --seeds 101 102 103 [--control-seeds 101 102 103]

Pool cells: the widest gap of a served token's logit below the float32
reference's best (the program), and of the token the float8 control
puts first at the same positions (the control).  Router cells: the
decisions that differ from the plain sequential rule, for the program
and for the rule computed in bfloat16 in its place.  All seeds run in
one process; each builds its own pool or router from its seed.
"""
import argparse
import gc
import json
import os
import sys
import time

HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path[:0] = [os.path.dirname(HERE), os.path.join(os.path.dirname(HERE), "src")]

import numpy as np  # noqa: E402

from chipbench.bench import (HERE as BENCH, Spans, cell_files,  # noqa: E402
                             enable_compile_cache, load_json, load_module,
                             require_chip, ROOT)


def pool_readings(run, control: bool):
    ref = load_module(os.path.join(BENCH, "configs", run.config["reference"]))
    members = run.config["members"]
    prog, ctrl = 0.0, 0.0
    for mi, member in enumerate(members):
        reqs = [(run.tokens[i][0], toks)
                for i, (m, toks) in sorted(run.served.items()) if m == mi]
        if not reqs:
            continue
        d = ref.dims(member)
        w = ref.make_weights(d, run.seed, mi, len(members))
        out = ref.served_gaps(d, w, reqs, length=run.config["cache_len"],
                              control=control)
        gaps, cgaps = out if control else (out, [])
        prog = max(prog, max(float(g.max()) for g in gaps))
        if control:
            ctrl = max(ctrl, max(float(g.max()) for g in cgaps))
        del w
        gc.collect()
    return {"program": prog, "control": ctrl if control else None,
            "tokens": sum(len(t) for _, t in run.served.values())}


def router_readings(run, control: bool):
    import ml_dtypes
    ref = load_module(os.path.join(BENCH, "configs", run.config["reference"]))
    p = ref.pool(run.config)
    prog = run.check()[0][1]
    if not control:
        return {"program": prog, "control": None}
    bf16 = lambda x: np.asarray(x, np.float64).astype(  # noqa: E731
        ml_dtypes.bfloat16).astype(np.float64)
    n = min(run.config["check"]["ticks"], len(run.log))
    picks = np.random.default_rng([run.seed, 9]).choice(len(run.log), n,
                                                        replace=False)
    bad = 0
    for j in sorted(picks):
        k, rng_state, _ = run.log[j]
        sla, up, rep = run.ticks[k]
        g = np.random.Generator(np.random.PCG64())
        g.bit_generator.state = rng_state
        r01 = ref.draws(int(g.integers(np.iinfo(np.int64).max)), run.batch)
        budgets = sla - 2.0 * up
        low = ref.route_tick(p, rep, budgets, r01, rd=bf16)
        bad += ref.mismatches(p, rep, budgets, r01, low)[0]
    return {"program": prog, "control": bad}


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, default=8.0)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--control-seeds", type=int, nargs="*", default=[])
    args = ap.parse_args(argv)
    bench = load_json(ROOT, "BENCHMARK.json")
    wl, config, traffic = cell_files(bench, args.workload)
    require_chip(wl["chips"])
    enable_compile_cache()
    driver = load_module(os.path.join(BENCH, "drivers",
                                      f"{config['driver']}.py"))
    rows = []
    for seed in args.seeds:
        t0 = time.perf_counter()
        run = driver.Run(config, traffic, seed, args.seconds, Spans())
        run.setup()
        run.window()
        run.release()
        control = seed in args.control_seeds
        read = pool_readings if config["driver"] == "pool" else router_readings
        row = {"seed": seed, **read(run, control),
               "seconds": time.perf_counter() - t0}
        print(json.dumps(row), flush=True)
        rows.append(row)
        del run
        gc.collect()
    prog = [r["program"] for r in rows]
    ctrl = [r["control"] for r in rows if r["control"] is not None]
    print(json.dumps({"workload": args.workload, "program_max": max(prog),
                      "control_min": min(ctrl) if ctrl else None}))


if __name__ == "__main__":
    main()
