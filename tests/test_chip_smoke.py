"""Rehearsal of ``chip_smoke.py`` on CPU: its phase functions at reduced
width (the script's ``main`` refuses anything but a TPU), and the
compile-cache placement the script and the launchers share."""

import importlib.util
import os
from pathlib import Path

os.environ.setdefault("XLA_FLAGS", "--xla_force_host_platform_device_count=8")

import jax
import pytest

from repro.configs.base import get_config
from repro.launch import compile_cache

_spec = importlib.util.spec_from_file_location(
    "chip_smoke", Path(__file__).resolve().parents[1] / "chip_smoke.py"
)
chip_smoke = importlib.util.module_from_spec(_spec)
_spec.loader.exec_module(chip_smoke)


@pytest.fixture(scope="module")
def cfg_prompts():
    cfg = get_config(chip_smoke.ARCH, reduced=True)
    return cfg, chip_smoke.make_prompts(cfg.vocab, n=6, lens=(5, 20))


def test_serving_phase_reduced(cfg_prompts):
    cfg, prompts = cfg_prompts
    stats = chip_smoke.serving_phase(cfg, prompts=prompts, new_tokens=5, n_slots=3,
                                     max_len=64)
    assert stats["tokens"] == 6 * 5
    assert stats["prefills"] >= 2 and stats["decode_steps"] >= 4


def test_elastic_phase_reduced_on_4_devices(cfg_prompts):
    if len(jax.devices()) < 4:
        pytest.skip("needs 4 virtual devices")
    cfg, prompts = cfg_prompts
    stats = chip_smoke.elastic_phase(
        cfg, jax.devices()[:4], prompts=prompts, new_tokens=6, n_slots=4, max_len=64,
        fault_step=3, lost=2,
    )
    assert stats["migration"]["step"] == 3 and stats["migration"]["migrated_slots"] >= 1
    # the CPU harness holds the streams bit for bit
    assert stats["identical_streams"], stats["divergences"]


def test_main_refuses_a_host_without_tpu():
    assert jax.devices()[0].platform != "tpu"
    with pytest.raises(SystemExit) as exc:
        chip_smoke.main([])
    assert exc.value.code not in (0, None)


def test_compile_cache_obeys_environment_then_fixed_repo_path(monkeypatch):
    """Only the choice of directory is exercised: ``jax.config.update`` is
    intercepted, so the test process never turns the cache on."""
    calls = []
    monkeypatch.setattr(compile_cache.jax.config, "update",
                        lambda name, value: calls.append((name, value)))
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "/elsewhere/cache")
    assert compile_cache.enable_compile_cache() == "/elsewhere/cache"
    assert calls == []  # JAX reads the variable itself

    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR")
    first = compile_cache.enable_compile_cache()
    assert first == compile_cache.enable_compile_cache()
    repo = Path(__file__).resolve().parents[1]
    assert Path(first) == repo / ".jax_cache"
    assert calls == [("jax_compilation_cache_dir", first)] * 2
    assert ".jax_cache/" in (repo / ".gitignore").read_text().split()
