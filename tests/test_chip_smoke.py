"""``chip_smoke.py`` off the chip, and the compile-cache helper.

The smoke must refuse to run anywhere but on a TPU; its phases are
driven here at the smoke config on the CPU, with the two facts only a
chip has (native kernels in the compiled HLO, device memory stats)
stubbed, so a change to the library entry points it calls shows up in
tier 1 rather than on the chip.
"""
from __future__ import annotations

import importlib.util
import os
import subprocess
import sys

import jax
import pytest

from repro.configs import get_smoke_config
from repro.launch import compile_cache

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
SCRIPT = os.path.join(ROOT, "chip_smoke.py")


def _run(env_extra: dict) -> subprocess.CompletedProcess:
    env = {k: v for k, v in os.environ.items() if k != "REPRO_FORCE_REF"}
    env.update(env_extra)
    return subprocess.run([sys.executable, SCRIPT], cwd=ROOT, env=env,
                          capture_output=True, text=True, timeout=300)


@pytest.mark.parametrize("env,why", [
    ({"JAX_PLATFORMS": "cpu"}, "no TPU"),
    ({"JAX_PLATFORMS": "cpu", "REPRO_FORCE_REF": "1"}, "REPRO_FORCE_REF"),
])
def test_chip_smoke_refuses_to_run_off_the_chip(env, why):
    out = _run(env)
    assert out.returncode != 0
    assert why in out.stderr
    assert '"ok"' not in out.stdout and "train:" not in out.stdout


@pytest.fixture
def smoke(monkeypatch):
    spec = importlib.util.spec_from_file_location("chip_smoke", SCRIPT)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    check = mod.check

    def cpu_check(ok, what):
        if "tpu_custom_call" not in what:     # the CPU compiles no Mosaic
            check(ok, what)

    monkeypatch.setattr(mod, "check", cpu_check)
    monkeypatch.setattr(mod, "peak_in_use", lambda: "n/a")
    return mod


def test_chip_smoke_train_phase_at_smoke_size(smoke):
    facts = {}
    cfg = get_smoke_config(smoke.ARCH).replace(num_layers=3,
                                               param_dtype="float32")
    smoke.phase_train(facts, cfg=cfg, limit=1 << 40, seq=32, start_layers=2)
    t = facts["train"]
    assert t["layers"] == 3 and t["pallas_calls"] == 2
    assert t["losses"][-1] < t["losses"][0]


def test_chip_smoke_serve_phase_at_smoke_size(smoke):
    facts = {}
    cfg = get_smoke_config(smoke.ARCH).replace(param_dtype="bfloat16",
                                               compute_dtype="bfloat16")
    smoke.phase_decode_kernel(facts, cfg=cfg, max_len=128)
    smoke.phase_serve(facts, cfg=cfg, max_len=128)
    s = facts["serve"]
    assert s["kernel_decode_compilations"] == 1
    assert s["greedy_common_prefix"] == s["generated"]
    assert facts["decode_kernel"]["kv_append_exact"]


FOUR_SCRIPT = r"""
import importlib.util, sys
spec = importlib.util.spec_from_file_location("chip_smoke", sys.argv[1])
mod = importlib.util.module_from_spec(spec)
spec.loader.exec_module(mod)
from repro.configs import get_smoke_config
facts = {}
cfg = get_smoke_config(mod.ARCH).replace(param_dtype="float32",
                                         compute_dtype="bfloat16")
mod.phase_four_chips(facts, cfg=cfg, seq=32, layers=2)
f = facts["four_chips"]
assert f["pallas_calls"] == 2 and f["batch_shards"] == "own", f
print("FOUR_CHIPS_PHASE_OK")
"""


def test_chip_smoke_four_chip_phase_on_four_cpu_devices():
    env = dict(os.environ,
               XLA_FLAGS="--xla_force_host_platform_device_count=4",
               PYTHONPATH=os.path.join(ROOT, "src"))
    out = subprocess.run([sys.executable, "-c", FOUR_SCRIPT, SCRIPT],
                         cwd=ROOT, env=env, capture_output=True, text=True,
                         timeout=600)
    assert "FOUR_CHIPS_PHASE_OK" in out.stdout, out.stdout + out.stderr


def test_compile_cache_keeps_the_env_dir(monkeypatch, tmp_path):
    before = jax.config.jax_compilation_cache_dir
    monkeypatch.setenv(compile_cache.ENV_VAR, str(tmp_path))
    assert compile_cache.enable() == str(tmp_path)
    assert jax.config.jax_compilation_cache_dir == before


def test_compile_cache_defaults_to_the_ignored_checkout_dir(monkeypatch):
    monkeypatch.delenv(compile_cache.ENV_VAR, raising=False)
    before = jax.config.jax_compilation_cache_dir
    try:
        path = compile_cache.enable()
        assert jax.config.jax_compilation_cache_dir == path
    finally:
        jax.config.update("jax_compilation_cache_dir", before)
    assert path == os.path.join(ROOT, ".jax_cache")
    with open(os.path.join(ROOT, ".gitignore")) as f:
        assert ".jax_cache/" in f.read().split()
