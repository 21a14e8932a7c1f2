"""Launcher plumbing: the published-width flag, the persistent-cache
placement, and importing the launchers without changing JAX state."""
import os
import pathlib
import subprocess
import sys

import jax
import pytest

from repro.configs import get_config, get_smoke_config
from repro.launch import compile_cache, fed_train, fleet_cli, sim

REPO = pathlib.Path(__file__).resolve().parents[1]


@pytest.mark.parametrize("launcher", [fed_train, sim])
@pytest.mark.parametrize("published", [False, True])
def test_published_flag_selects_the_config(launcher, published):
    argv = ["--arch", "qwen1.5-0.5b"] + (["--published"] if published
                                         else [])
    cfg = fleet_cli.arch_config(launcher.build_parser().parse_args(argv))
    want = (get_config if published else get_smoke_config)("qwen1.5-0.5b")
    assert cfg == want
    assert cfg.num_layers == (24 if published else 2)


def test_cache_env_var_wins_and_nothing_else_is_set(monkeypatch):
    before = jax.config.jax_compilation_cache_dir
    monkeypatch.setenv(compile_cache.ENV_VAR, "/placed/from/outside")
    assert compile_cache.enable_compile_cache() == "/placed/from/outside"
    assert jax.config.jax_compilation_cache_dir == before


def test_cache_falls_back_to_a_fixed_checkout_path(monkeypatch):
    before = jax.config.jax_compilation_cache_dir
    monkeypatch.delenv(compile_cache.ENV_VAR, raising=False)
    try:
        first = compile_cache.enable_compile_cache()
        assert first == str(REPO / ".jax_cache")
        assert jax.config.jax_compilation_cache_dir == first
        assert compile_cache.enable_compile_cache() == first
    finally:
        jax.config.update("jax_compilation_cache_dir", before)


IMPORT_CHECK = r"""
import os
before = os.environ.get("XLA_FLAGS")
import jax
import repro.launch.dryrun, repro.launch.perf
import repro.launch.fed_train, repro.launch.sim
assert os.environ.get("XLA_FLAGS") == before, os.environ.get("XLA_FLAGS")
assert jax.config.jax_compilation_cache_dir is None
assert jax.device_count() == 1
print("IMPORT_STATE_OK")
"""


def test_importing_launchers_changes_no_jax_state():
    env = {k: v for k, v in os.environ.items()
           if k not in ("XLA_FLAGS", compile_cache.ENV_VAR)}
    env.update(JAX_PLATFORMS="cpu", PYTHONPATH=str(REPO / "src"))
    res = subprocess.run([sys.executable, "-c", IMPORT_CHECK], env=env,
                         cwd=REPO, capture_output=True, text=True,
                         timeout=300)
    assert "IMPORT_STATE_OK" in res.stdout, res.stdout + res.stderr[-3000:]
