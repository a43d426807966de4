"""Small stand-ins of the benchmark's cells, for the CPU tests: the same
drivers, traffic generator and references, at reduced width."""
from __future__ import annotations

import copy
import dataclasses
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
for p in (ROOT, os.path.join(ROOT, "src")):
    if p not in sys.path:
        sys.path.insert(0, p)

from chipbench.bench import cell_files, load_json  # noqa: E402

WIDTHS = {"n_layers": 4, "d_model": 256, "n_heads": 4, "n_kv_heads": 2,
          "head_dim": 64, "d_ff": 512, "vocab_size": 32768}


def small_registry(monkeypatch):
    """Make the registry's pool members small; returns nothing."""
    from repro.configs import registry
    real = registry.get_config

    def get_config(arch):
        return dataclasses.replace(real(arch), **WIDTHS)
    monkeypatch.setattr(registry, "get_config", get_config)


def small_cell(workload: str):
    """``(bench, config, traffic)`` of ``workload`` at a CPU test's size."""
    bench = load_json(ROOT, "BENCHMARK.json")
    _, config, traffic = cell_files(bench, workload)
    config, traffic = copy.deepcopy(config), copy.deepcopy(traffic)
    if config["driver"] == "pool":
        for m in config["members"]:
            m.update(WIDTHS)
        traffic["token_ids"] = WIDTHS["vocab_size"]
        traffic["fields"]["prompt_len"]["values"] = [8, 16, 32, 64]
        traffic["warmup"] = {"prompt_len": 16, "n_decode": 2}
        config["cache_len"] = 80
    else:
        traffic["arrivals"]["batch"] = 256
        traffic["arrivals"]["distinct_ticks"] = 2
        config["check"]["ticks"] = 2
    return bench, config, traffic
