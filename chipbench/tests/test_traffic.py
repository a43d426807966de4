"""The traffic generator: one set of independent draws, ordered by the
run's seed."""
import numpy as np
import pytest

import small  # noqa: F401  (puts the checkout on the path)
from chipbench import traffic
from chipbench.bench import HERE, load_json


def _mix(name):
    return load_json(HERE, "traffic", f"{name}.json")


@pytest.mark.parametrize("name", ["steady", "burst"])
def test_a_seed_fixes_its_requests(name):
    mix = _mix(name)
    a, a2 = (traffic.open_loop(mix, 30.0, 2**31 + 99) for _ in range(2))
    b = traffic.open_loop(mix, 30.0, 2**31 + 100)
    for k in a:
        np.testing.assert_array_equal(a[k], a2[k])
    assert not np.array_equal(a["arrival_s"][:10], b["arrival_s"][:10])


@pytest.mark.parametrize("name", ["steady", "burst"])
def test_every_seed_serves_the_same_work(name):
    """Each seed sends the mix's count of requests, from one set of gaps
    and of column values, in an order of its own."""
    mix = _mix(name)
    arr = mix["arrivals"]
    bursts = round(arr["rate_rps"] * 51.0 / arr["burst"])
    runs = [traffic.open_loop(mix, 51.0, 2**31 + s) for s in range(6)]
    for r in runs:
        assert len(r["arrival_s"]) == bursts * arr["burst"]
        assert r["arrival_s"][0] == 0.0 and r["arrival_s"][-1] < 51.0
        assert np.all(np.diff(r["arrival_s"]) >= 0)
        np.testing.assert_allclose(
            np.sort(np.diff(np.unique(r["arrival_s"]))),
            np.sort(np.diff(np.unique(runs[0]["arrival_s"]))), atol=1e-9)
        for k in mix["fields"]:
            np.testing.assert_array_equal(np.sort(r[k]), np.sort(runs[0][k]))
    assert not np.array_equal(runs[0]["prompt_len"], runs[1]["prompt_len"])


def test_rate_and_columns():
    mix = _mix("steady")
    r = traffic.open_loop(mix, 5000.0, 7)
    n = len(r["prompt_len"])
    assert n == round(mix["arrivals"]["rate_rps"] * 5000.0)
    p = mix["fields"]["prompt_len"]["probs"]
    for v, q in zip((64, 128, 256, 512), p):
        assert abs((r["prompt_len"] == v).mean() - q) < 0.01
    assert set(np.unique(r["n_decode"])) == set(range(1, 16))
    assert 150.0 <= r["sla_ms"].min() and r["sla_ms"].max() < 600.0
    assert r["uplink_ms"].min() >= 0.1
    assert abs(r["uplink_ms"].mean() - 57.87) < 1.0


def test_gaps_are_exponential():
    """Gaps between arrivals have the mean of the rate and a coefficient
    of variation of 1, and short gaps cluster as a Poisson process's do."""
    mix = _mix("steady")
    rate = mix["arrivals"]["rate_rps"]
    gaps = np.diff(traffic.open_loop(mix, 5000.0, 7)["arrival_s"])
    assert abs(gaps.mean() * rate - 1.0) < 0.03
    assert abs(gaps.std() / gaps.mean() - 1.0) < 0.05
    short = (gaps < 0.1 / rate).mean()
    assert abs(short - (1 - np.exp(-0.1))) < 0.01


def test_bursts_arrive_together():
    mix = _mix("burst")
    r = traffic.open_loop(mix, 40.0, 3)
    b = mix["arrivals"]["burst"]
    starts = r["arrival_s"].reshape(-1, b)
    assert np.all(starts == starts[:, :1])
    assert np.all(np.diff(starts[:, 0]) > 0)


def test_ticks_and_uplink_schedule():
    mix = _mix("route4096")
    a = traffic.columns(mix["fields"], 4096, [5, 0])
    b = traffic.columns(mix["fields"], 4096, [6, 0])
    assert not np.array_equal(a["sla_ms"], b["sla_ms"])
    np.testing.assert_array_equal(
        a["sla_ms"], traffic.columns(mix["fields"], 4096, [5, 0])["sla_ms"])
    s, s2 = (traffic.shuffled(a, [9]) for _ in range(2))
    np.testing.assert_array_equal(s["sla_ms"], s2["sla_ms"])
    np.testing.assert_array_equal(np.sort(s["sla_ms"]), np.sort(a["sla_ms"]))
    assert not np.array_equal(s["sla_ms"], a["sla_ms"])
    up = traffic.ScheduledUplink([3.0, 1.0, 2.0])
    rng = np.random.default_rng(0)
    state = rng.bit_generator.state
    assert [float(up.sample(rng, 1)[0]) for _ in range(3)] == [3.0, 1.0, 2.0]
    assert rng.bit_generator.state == state
