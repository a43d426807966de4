"""The router cell's control at a small size: the sequential rule
computed in bfloat16 in the program's place differs from the float64
rule where the program does not.  (The pool cells' control is in
``test_reference.py``; both are read at the cells' own size on the
chip by ``tools/calibrate.py``.)"""
import os

import small
from chipbench.bench import HERE, Spans, load_module

calibrate = load_module(os.path.join(HERE, "tools", "calibrate.py"))


def test_router_control_fails_where_the_program_passes(monkeypatch):
    from repro.kernels import policy_select
    policy_select._charged_jit.cache_clear()
    _, config, traffic = small.small_cell("zoo11.route4096")
    driver = load_module(os.path.join(HERE, "drivers", "router.py"))
    run = driver.Run(config, traffic, 2**31 + 3, 0.5, Spans())
    run.setup()
    run.window()
    run.release()
    got = calibrate.router_readings(run, control=True)
    assert got["program"] == 0
    assert got["control"] > 10
