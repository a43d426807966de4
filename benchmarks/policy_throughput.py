"""Selection-throughput microbenchmark: scalar loop vs numpy-batched vs
jitted/Pallas ModiPick on the Table-2 zoo.

The paper puts selection on the hot path of every inference (§3.3), so
selections/sec bounds how much traffic one router can carry and how big
a sweep the simulators can afford.  Rows:

    policy_throughput/<impl>/batch_<B>

with ``us_per_call`` = microseconds per selection and ``derived``
carrying ``selps`` (selections/sec) plus ``speedup`` vs the scalar loop
at the same batch size.  ``benchmarks/run.py --json`` records the rows
in ``BENCH_policy_throughput.json`` so the perf trajectory is tracked
across PRs.
"""
from __future__ import annotations

import time
from typing import List, Sequence, Tuple

Row = Tuple[str, float, str]

BATCHES = (1, 1_000, 100_000)
FAST_BATCHES = (1, 1_000)
SLA_MS = 250.0
SCALAR_CAP = 5_000   # scalar rate is measured on at most this many calls
REPEATS = 3
SEED = 23


def _budgets(rng, n: int):
    import numpy as np
    t_input = np.clip(rng.normal(50.0, 25.0, size=n), 0.0, None)
    return np.maximum(SLA_MS - 2.0 * t_input, 5.0)


def _best_rate(fn, n: int, repeats: int = REPEATS) -> float:
    """Best-of-N selections/sec for ``fn()`` covering ``n`` selections."""
    best = float("inf")
    for _ in range(repeats):
        t0 = time.perf_counter()
        fn()
        best = min(best, time.perf_counter() - t0)
    return n / best


def bench_rows(fast: bool = False,
               batches: Sequence[int] = None) -> List[Row]:
    import numpy as np

    from repro.core import policy_vec
    from repro.core.policy import ModiPick
    from repro.core.zoo import TABLE2, make_store

    batches = tuple(batches or (FAST_BATCHES if fast else BATCHES))
    store = make_store(TABLE2)
    policy = ModiPick(t_threshold=20.0)
    rng = np.random.default_rng(SEED)
    rows: List[Row] = []
    for B in batches:
        budgets = _budgets(rng, B)

        m = min(B, SCALAR_CAP)
        scalar_rng = np.random.default_rng(0)

        def scalar():
            for b in budgets[:m]:
                policy.select(store, float(b), scalar_rng)

        scalar_selps = _best_rate(scalar, m)
        rows.append((f"policy_throughput/scalar/batch_{B}",
                     1e6 / scalar_selps,
                     f"selps={scalar_selps:.0f};measured_n={m}"))

        for backend in ("numpy", "jax"):
            # Generator construction stays outside the timed region,
            # matching the scalar loop's pre-built rng — the rows
            # measure selection, not np.random.default_rng().
            brng = np.random.default_rng(1)
            run = lambda: policy.select_batch(  # noqa: E731
                store, budgets, brng, backend=backend)
            run()  # warm-up (jit compile for the jax path)
            selps = _best_rate(run, B)
            rows.append((f"policy_throughput/{backend}/batch_{B}",
                         1e6 / selps,
                         f"selps={selps:.0f};"
                         f"speedup={selps / scalar_selps:.1f}x"))
    return rows


if __name__ == "__main__":
    print("name,us_per_call,derived")
    for row in bench_rows():
        print(f"{row[0]},{row[1]:.3f},{row[2]}")
