"""The trace reduction, on a small hand-made trace with the planes, lines
and names a TPU trace has."""
import pytest

import small  # noqa: F401
from chipbench import trace

DEV, HOST = "/device:TPU:0", "/host:CPU"


def ev(plane, line, name, start, dur):
    return {"plane": plane, "line": line, "name": name, "start_ns": start,
            "dur_ns": dur}


def events():
    return [
        ev(DEV, "XLA Modules", "jit_prefill_step(3)", 100, 300),
        ev(DEV, "XLA Ops", "fusion.1", 100, 100),
        ev(DEV, "XLA Ops", "fusion.2", 150, 150),     # overlaps fusion.1
        ev(DEV, "XLA Modules", "jit_decode_step(4)", 600, 100),
        ev(DEV, "XLA Ops", "dot.3", 600, 100),
        ev(DEV, "XLA Modules", "jit_decode_step(4)", 900, 250),
        ev(DEV, "XLA Ops", "dot.3", 900, 250),        # runs past the window
        ev(HOST, "python", "pool.execute", 60, 890),
        ev(HOST, "python", "pool.route", 400, 100),
        ev(HOST, "python", "pool.idle", 960, 40),
    ]


def test_busy_programs_and_gaps():
    r = trace.reduce_events(events(), 0, 1000)
    assert r.window_s == pytest.approx(1e-6)
    # busy: [100, 300) + [600, 700) + [900, 1000) clipped
    assert r.busy_s == pytest.approx(400e-9)
    assert sum(d for _, d in r.programs["decode_step"]) == 350
    assert r.device_ops()[0][0] == "decode_step"
    labels = {(lab, s, n) for lab, s, n in r.gaps}
    assert ("outside", 0, 100) in labels                # before any span
    assert ("pool.route", 300, 300) in labels           # midpoint 450
    assert ("pool.execute", 700, 200) in labels         # midpoint 800
    gaps = dict(map(tuple, r.idle_gaps()))
    assert gaps["pool.route"] == pytest.approx(300e-9)


def test_program_names():
    assert trace.program_name("jit_decode_step(12)") == "decode_step"
    assert trace.program_name("jit_run") == "run"


def test_no_device_plane_is_an_error():
    with pytest.raises(RuntimeError):
        trace.reduce_events([ev(HOST, "python", "pool.idle", 0, 10)], 0, 10)


def test_recorded_chip_trace():
    """A trace recorded on a v5e: two jitted programs, ``prefill_step``
    and ``decode_step``, called four times each under host spans
    ``bench.request`` ⊃ ``bench.model``."""
    path = f"{small.ROOT}/chipbench/tests/data/two_programs.xplane.pb"
    events = trace.load_xplane(path, ("bench.",))
    spans = [e for e in events if e["name"] == "bench.request"]
    assert len(spans) == 4
    t0 = spans[0]["start_ns"]
    t1 = spans[-1]["start_ns"] + spans[-1]["dur_ns"]
    r = trace.reduce_events(events, t0, t1)
    assert {k: len(v) for k, v in r.programs.items()} == {
        "prefill_step": 4, "decode_step": 4}
    ops = [e for e in events if e["line"] == trace.OPS_LINE]
    assert 0 < r.busy_s <= sum(e["dur_ns"] for e in ops) * 1e-9
    assert r.busy_s < r.window_s
    labels = {g[0] for g in r.gaps}
    assert labels <= {"bench.request", "bench.model", "outside"}
    assert sum(g[2] for g in r.gaps) * 1e-9 == pytest.approx(
        r.window_s - r.busy_s, rel=1e-6)
