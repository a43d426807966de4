"""chip_smoke.py's phases on the CPU at reduced width.

The phases are the same functions the script runs on the chip; here the
pool members are the registry configs' ``reduced()`` forms (still bf16,
the configs' own dtype), and the batches are smaller.  Subprocess tests
cover what the script does as a whole: importing it touches no device,
and without a TPU it exits non-zero before building anything.
"""
import json
import os
import subprocess
import sys

import jax
import pytest

import chip_smoke
from repro.configs.registry import get_config
from repro.launch import serve
from repro.launch.compile_cache import DEFAULT_DIR

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
TOKENS = (2, 16)


def _run(code, env=None, timeout=300):
    env = dict(os.environ if env is None else env,
               PYTHONPATH=os.path.join(ROOT, "src"))
    return subprocess.run([sys.executable, "-c", code], env=env, cwd=ROOT,
                          capture_output=True, text=True, timeout=timeout)


@pytest.fixture(scope="module")
def tokens():
    return chip_smoke._tokens(TOKENS, 0)


@pytest.fixture(scope="module")
def variants(tokens):
    cfgs = [get_config(a).reduced() for a in serve.DEFAULT_ARCHS]
    return chip_smoke.phase_pool(cfgs, tokens)


def test_import_touches_no_device():
    proc = _run("import chip_smoke\n"
                "from jax._src import xla_bridge\n"
                "assert not xla_bridge.backends_are_initialized()\n"
                "print('clean')")
    assert proc.returncode == 0, proc.stderr[-2000:]
    assert "clean" in proc.stdout


def test_exits_nonzero_without_tpu():
    proc = subprocess.run([sys.executable, "chip_smoke.py"], cwd=ROOT,
                          capture_output=True, text=True, timeout=300,
                          env=dict(os.environ, JAX_PLATFORMS="cpu"))
    assert proc.returncode != 0
    assert "needs a TPU" in proc.stderr
    assert "[pool]" not in proc.stdout and '"ok"' not in proc.stdout


def test_pool_phase_builds_members_in_config_dtype(variants):
    assert [v.name for v in variants] == [
        get_config(a).reduced().name for a in serve.DEFAULT_ARCHS]
    for v in variants:
        assert v.quality == v.cfg.quality
        assert {str(x.dtype) for x in jax.tree.leaves(v.params)} \
            == {"bfloat16"}


def test_cache_agreement_phase(variants):
    worst = chip_smoke.phase_cache_agreement(
        variants, chip_smoke._tokens((TOKENS[0], TOKENS[1] + 1), 1))
    assert set(worst) == {v.name for v in variants}
    assert all(r < chip_smoke.CACHE_TOL for r in worst.values())


def test_serve_phase(variants, tokens):
    ex = chip_smoke.phase_serve(variants, tokens, n_requests=8)
    assert len(ex.results) == 8
    assert ex.summary()["n"] == 8
    assert ex.router.n_routed == 8


def test_serve_launcher_main(monkeypatch, capsys):
    """``python -m repro.launch.serve`` end to end, on reduced configs."""
    monkeypatch.setattr(serve, "get_config",
                        lambda arch: get_config(arch).reduced())
    monkeypatch.setattr(serve, "enable_compile_cache", lambda: None)
    serve.main(["--requests", "3", "--batch", "1", "--seq", "8"])
    out = capsys.readouterr().out
    assert out.count("req ") == 3
    assert json.loads(out[out.index("{"):])["n"] == 3


def test_selection_phase():
    chip_smoke.phase_selection(batches=(300, 5000), charged_batch=512)


def test_fleet_phase_on_four_virtual_devices():
    env = dict(os.environ,
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    proc = _run("import chip_smoke\nchip_smoke.phase_fleet_sharded()",
                env=env)
    assert proc.returncode == 0, proc.stdout[-2000:] + proc.stderr[-2000:]
    assert "output shards on 4 devices" in proc.stdout
    assert "picks bit-identical" in proc.stdout


_CACHE_PROBE = """
import json
import jax, jax.numpy as jnp
from repro.launch.compile_cache import enable_compile_cache
path = enable_compile_cache()
jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
jax.jit(lambda x: jnp.sin(x) * 3)(jnp.arange(7.0)).block_until_ready()
print(json.dumps({"path": path,
                  "config": jax.config.jax_compilation_cache_dir}))
"""


def test_compile_cache_follows_env(tmp_path):
    env = dict(os.environ, JAX_COMPILATION_CACHE_DIR=str(tmp_path))
    proc = _run(_CACHE_PROBE, env=env)
    assert proc.returncode == 0, proc.stderr[-2000:]
    got = json.loads(proc.stdout.strip().splitlines()[-1])
    assert got == {"path": str(tmp_path), "config": str(tmp_path)}
    assert any(tmp_path.iterdir()), "no compile landed in the env's dir"


def test_compile_cache_defaults_to_checkout():
    env = {k: v for k, v in os.environ.items()
           if k != "JAX_COMPILATION_CACHE_DIR"}
    proc = _run("import json, jax\n"
                "from repro.launch.compile_cache import enable_compile_cache\n"
                "p = enable_compile_cache()\n"
                "print(json.dumps([p, jax.config.jax_compilation_cache_dir]))",
                env=env)
    assert proc.returncode == 0, proc.stderr[-2000:]
    path, config = json.loads(proc.stdout.strip().splitlines()[-1])
    assert path == config == DEFAULT_DIR == os.path.join(ROOT, ".jax_cache")
