"""Process-entry contract: who picks the platform, where the compile cache
lives, and that chip_smoke.py refuses a machine without a TPU."""
import os
import subprocess
import sys

import jax
import pytest

from kungfu_tpu import env as kfenv

_REPO = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


@pytest.fixture
def config_updates(monkeypatch):
    """Record jax.config.update calls instead of applying them."""
    from kungfu_tpu.utils import trace

    calls = []
    monkeypatch.setattr(jax.config, "update", lambda k, v: calls.append((k, v)))
    # enable_compile_cache() also lets its process write a start record:
    # this one is pytest's, not an entry point, so the permission goes again
    monkeypatch.setattr(trace, "_start_record_dir", "")
    return calls


@pytest.mark.parametrize("jax_platforms", [None, "cpu"])
def test_jax_platforms_alone_decides(monkeypatch, config_updates, jax_platforms):
    monkeypatch.delenv("KFT_PLATFORM", raising=False)
    if jax_platforms is None:
        monkeypatch.delenv("JAX_PLATFORMS", raising=False)
    else:
        monkeypatch.setenv("JAX_PLATFORMS", jax_platforms)
    kfenv.apply_platform_override()
    assert config_updates == []  # JAX reads the variable itself


def test_launcher_contract_wins(monkeypatch, config_updates):
    monkeypatch.setenv("KFT_PLATFORM", "cpu")
    monkeypatch.setenv("JAX_PLATFORMS", "tpu")
    kfenv.apply_platform_override()
    assert config_updates == [("jax_platforms", "cpu")]


def test_compile_cache_placed_from_outside(monkeypatch, config_updates, tmp_path):
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    assert kfenv.enable_compile_cache() == str(tmp_path)
    assert config_updates == []


def test_compile_cache_default_is_fixed_in_checkout(monkeypatch, config_updates):
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    want = os.path.join(_REPO, ".jax_cache")
    assert kfenv.enable_compile_cache() == want
    assert kfenv.enable_compile_cache() == want  # no pid, time or temp name
    assert config_updates == [("jax_compilation_cache_dir", want)] * 2


def test_chip_smoke_refuses_cpu_and_parent_stays_off_jax():
    code = (
        "import sys; import chip_smoke; rc = chip_smoke.main([]); "
        "assert 'jax' not in sys.modules, 'the parent imported jax'; "
        "print('PARENT_NO_JAX'); sys.exit(rc)"
    )
    r = subprocess.run(
        [sys.executable, "-c", code], cwd=_REPO, capture_output=True, text=True,
        timeout=300, env=dict(os.environ, JAX_PLATFORMS="cpu"),
    )
    assert r.returncode != 0, r.stdout[-2000:]
    assert "PARENT_NO_JAX" in r.stdout, r.stdout[-2000:] + r.stderr[-2000:]
    assert '"ok": true' not in r.stdout  # no verdict without a TPU


def test_launcher_and_router_parents_start_no_backend():
    """One process owns a chip: the parents that spawn the workers must be
    importable, flags parsed, without a JAX backend coming up."""
    code = (
        "import kungfu_tpu.run.__main__, kungfu_tpu.run.launcher, "
        "kungfu_tpu.serving.__main__, kungfu_tpu.serving.router; "
        "from jax._src import xla_bridge; "
        "assert not xla_bridge._backends, list(xla_bridge._backends); "
        "print('NO_BACKEND')"
    )
    r = subprocess.run(
        [sys.executable, "-c", code], cwd=_REPO, capture_output=True, text=True,
        timeout=120, env=dict(os.environ, JAX_PLATFORMS="cpu"),
    )
    assert "NO_BACKEND" in r.stdout, r.stdout[-2000:] + r.stderr[-2000:]
