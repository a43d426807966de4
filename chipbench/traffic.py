"""The one traffic generator: reads a mix's data file and draws its
requests.

A mix file (``chipbench/traffic/<traffic>.json``) holds parameters only:

- ``arrivals``: ``{"process": "poisson", "rate_rps": r, "burst": b}``,
  open loop — bursts of ``b`` requests that arrive together, burst
  starts a Poisson process at a mean *request* rate ``r``; or
  ``{"process": "closed", "batch": n}`` — ticks of ``n`` rows routed
  back to back;
- ``fields``: one distribution per request column (see :func:`draw`).

Every seed serves the same work.  The values of each column, and an
open loop's gaps between arrivals, are independent draws made once for
the mix and the window from a stream of their own (``SET_STREAM``); the
run's seed puts each of them in an order of its own (:func:`shuffled`).
Two seeds send as many requests, as large, with SLAs and uplinks from
one set, but which request comes when, and how arrivals cluster,
differ.

The uplink and SLA arithmetic follows ModiPick (arXiv:1909.02053,
section 4): a one-way uplink drawn from a normal at the measured
campus-WiFi mean and standard deviation, floored at 0.1 ms, and
per-request SLAs uniform over a range.
"""
from __future__ import annotations

import math
from typing import Dict, Sequence

import numpy as np

# The stream of the draws that every seed shares, apart from any seed.
SET_STREAM = 0


def draw(spec: dict, n: int, rng: np.random.Generator) -> np.ndarray:
    """``n`` independent values of one column.

    ``dist`` is one of ``choice`` (``values``, ``probs``),
    ``uniform_int`` (``lo``..``hi`` inclusive), ``uniform`` (``lo``,
    ``hi``) or ``normal`` (``mean``, ``std``, ``floor``)."""
    kind = spec["dist"]
    if kind == "choice":
        p = np.asarray(spec["probs"], np.float64)
        return rng.choice(np.asarray(spec["values"]), n, p=p / p.sum())
    if kind == "uniform_int":
        return rng.integers(int(spec["lo"]), int(spec["hi"]) + 1, n)
    if kind == "uniform":
        return rng.uniform(spec["lo"], spec["hi"], n)
    if kind == "normal":
        return np.maximum(rng.normal(spec["mean"], spec["std"], n),
                          spec.get("floor", -math.inf))
    raise ValueError(f"unknown distribution {kind!r}")


def open_loop(traffic: dict, seconds: float, seed: int) -> Dict[str, np.ndarray]:
    """The requests of one open-loop run: ``arrival_s`` (from the start
    of the window, all inside ``[0, seconds)``) and one array per field.

    The window holds ``round(rate_rps * seconds / burst)`` bursts and
    opens with one.  A Poisson process that puts that many arrivals in
    the window puts them at independent uniform times, so the gaps
    between the sorted times are its gaps: they are drawn once, and the
    seed orders them."""
    arr = traffic["arrivals"]
    if arr["process"] != "poisson":
        raise ValueError(f"open_loop needs a poisson mix, not {arr['process']!r}")
    burst = int(arr.get("burst", 1))
    n = max(1, round(arr["rate_rps"] * seconds / burst))
    times = np.random.default_rng([SET_STREAM, 0]).uniform(0.0, seconds, n - 1)
    gaps = np.diff(np.concatenate([[0.0], np.sort(times)]))
    starts = np.concatenate([[0.0], np.cumsum(
        shuffled({"gap": gaps}, [seed, 0])["gap"])])
    out = shuffled(columns(traffic["fields"], n * burst, [SET_STREAM, 1]),
                   [seed, 1])
    out["arrival_s"] = np.repeat(starts, burst)
    return out


def columns(fields: dict, n: int, seed: Sequence[int]) -> Dict[str, np.ndarray]:
    """``n`` rows of each of ``fields`` (name → distribution), e.g. one
    closed-loop tick; ``seed`` is a sequence of whole numbers."""
    return {name: draw(fields[name], n, np.random.default_rng([*seed, i]))
            for i, name in enumerate(sorted(fields))}


def shuffled(cols: Dict[str, np.ndarray], seed: Sequence[int]) -> Dict[str, np.ndarray]:
    """Each column of ``cols`` in an order of its own, drawn from
    ``seed``."""
    return {name: np.random.default_rng([*seed, i]).permutation(cols[name])
            for i, name in enumerate(sorted(cols))}


class ScheduledUplink:
    """A network model whose draws are fixed in advance: the executor's
    ``sample(rng, n)`` hands out the next ``n`` of the traffic's uplink
    times, in order, and leaves ``rng`` alone."""

    def __init__(self, uplink_ms):
        self.values = [float(x) for x in uplink_ms]
        self.next = 0

    def sample(self, rng, n: int = 1) -> np.ndarray:
        out = np.asarray(self.values[self.next:self.next + n])
        self.next += n
        return out
