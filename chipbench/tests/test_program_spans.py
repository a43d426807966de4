"""The readers of the program's own spans and counters, on hand-made
reductions whose gaps carry program span names beside the benchmark's."""
from types import SimpleNamespace

import pytest

import small  # noqa: F401
from chipbench.bench import load_module, reader
from chipbench.trace import Reduced


def read(metric, run):
    return load_module(reader(metric)).read(run)


def reduced(gaps, busy_s=2.0):
    return Reduced(window_s=10.0, busy_s=busy_s,
                   gaps=[(label, 0, ns) for label, ns in gaps])


def test_tick_host_idle_counts_router_program_spans_only():
    gaps = [("router.select.readback", 2_000_000), ("router.apply", 6_000_000),
            ("router.route_batch", 1_000_000), ("router.select.pack", 500_000),
            ("router.select.draw", 500_000),
            # the benchmark's own labels
            ("router.tick", 50_000_000), ("router.charged_select", 9_000_000),
            ("outside", 7_000_000)]
    run = SimpleNamespace(trace=reduced(gaps), log=[None] * 4)
    assert read("tick_host_idle_ms.select", run) == pytest.approx(10.0 / 4)
    run.trace = reduced(gaps[5:])
    assert read("tick_host_idle_ms.select", run) is None
    run.trace = None
    assert read("tick_host_idle_ms.select", run) is None


def test_exec_host_idle_counts_serving_program_spans_only():
    gaps = [("pool.exec", 1_000_000), ("pool.exec.route", 200_000),
            ("pool.exec.observe", 300_000), ("pool.run", 400_000),
            ("pool.run.upload", 500_000), ("pool.run.sync", 600_000),
            # the benchmark's own labels
            ("pool.route", 9_000_000), ("pool.execute", 9_000_000),
            ("pool.idle", 900_000_000), ("pool.decode", 1_000),
            ("outside", 5_000_000)]
    run = SimpleNamespace(trace=reduced(gaps), attempted=10)
    assert read("exec_host_idle_ms.pool", run) == pytest.approx(3.0 / 10)
    run.trace = reduced(gaps[6:])
    assert read("exec_host_idle_ms.pool", run) is None


def test_tokens_per_busy_s_counts_served_tokens():
    results = [SimpleNamespace(tokens_served=n) for n in (4, 16, 0, 2)]
    run = SimpleNamespace(trace=reduced([], busy_s=0.5),
                          ex=SimpleNamespace(results=results))
    assert read("tokens_per_busy_s.pool", run) == pytest.approx(22 / 0.5)
    # a program that does not count served tokens reports nothing
    run.ex.results = [SimpleNamespace(variant="a")]
    assert read("tokens_per_busy_s.pool", run) is None
    run.ex.results = [SimpleNamespace(tokens_served=0)]
    assert read("tokens_per_busy_s.pool", run) is None
