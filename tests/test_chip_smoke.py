"""chip_smoke.py cannot be run here (it demands the chip), so what can rot
between chip runs is pinned on the CPU: the no-fallback guarantee, one
phase rehearsed at tiny size, and the compile-cache placement rule every
entry point shares."""

import importlib.util
import os
import subprocess
import sys
from pathlib import Path

import jax
import pytest

from realtime_fraud_detection_tpu.utils.compile_cache import (
    CACHE_DIR_ENV,
    DEFAULT_CACHE_DIR,
    configure_compile_cache,
)

REPO_ROOT = Path(__file__).resolve().parents[1]


@pytest.fixture(scope="module")
def smoke():
    spec = importlib.util.spec_from_file_location(
        "chip_smoke_under_test", REPO_ROOT / "chip_smoke.py")
    mod = importlib.util.module_from_spec(spec)
    sys.modules[spec.name] = mod      # its dataclass looks itself up here
    spec.loader.exec_module(mod)
    return mod


def test_no_chip_means_non_zero_exit_and_no_result():
    proc = subprocess.run(
        [sys.executable, str(REPO_ROOT / "chip_smoke.py")],
        capture_output=True, text=True, timeout=300, cwd=str(REPO_ROOT),
        env={**os.environ, "JAX_PLATFORMS": "cpu"})
    assert proc.returncode != 0
    assert "platform 'cpu'" in proc.stderr
    assert '"ok"' not in proc.stdout


def test_stream_phase_rehearsal_at_tiny_size(smoke):
    size = smoke.TINY
    assert size.n_stream == 64
    gen = smoke.make_generator(size, seed=0)
    scorer = smoke.make_scorer(size, 0, gen)
    out = smoke.stream_phase(scorer, gen, size)
    assert out["scored"] == 64


def test_a_failed_check_fails_its_phase(smoke):
    checks = smoke.Checks("demo")
    checks.check("holds", True)
    checks.check("does not hold", False, "detail")
    with pytest.raises(smoke.PhaseFailed, match="does not hold"):
        checks.done()
    assert smoke.run_phases([("demo", checks.done)]) == ["demo"]


def test_full_size_is_the_deployed_width(smoke):
    from realtime_fraud_detection_tpu.models.bert import BertConfig

    full = smoke.FULL
    assert full.bert_config == BertConfig() and full.text_len == 64
    assert (full.num_users, full.num_merchants) == (10_000, 5_000)
    assert full.n_stream >= 4096 and full.max_batch == 256


@pytest.fixture
def _restore_cache_config():
    before = jax.config.jax_compilation_cache_dir
    yield
    jax.config.update("jax_compilation_cache_dir", before)


def test_cache_dir_from_outside_wins(monkeypatch, tmp_path,
                                     _restore_cache_config):
    monkeypatch.setenv(CACHE_DIR_ENV, str(tmp_path))
    assert configure_compile_cache() == str(tmp_path)
    assert jax.config.jax_compilation_cache_dir == str(tmp_path)
    assert os.environ[CACHE_DIR_ENV] == str(tmp_path)


def test_cache_key_covers_metadata_unless_set_from_outside(
        monkeypatch, _restore_cache_config):
    """A cached program must carry THIS source's op_names (the device
    program is read by its named scopes), so the entry points put the
    metadata in the key; an outside setting wins, as conftest's does."""
    from realtime_fraud_detection_tpu.utils.compile_cache import (
        METADATA_IN_KEY_ENV,
    )

    flag = "jax_compilation_cache_include_metadata_in_key"
    before = getattr(jax.config, flag)
    try:
        assert os.environ[METADATA_IN_KEY_ENV] == "0"      # conftest.py
        configure_compile_cache()
        assert getattr(jax.config, flag) is False
        monkeypatch.delenv(METADATA_IN_KEY_ENV)
        configure_compile_cache()
        assert os.environ[METADATA_IN_KEY_ENV] == "1"
        assert getattr(jax.config, flag) is True
    finally:
        jax.config.update(flag, before)


def test_cache_dir_default_is_fixed_inside_the_checkout(
        monkeypatch, _restore_cache_config):
    monkeypatch.delenv(CACHE_DIR_ENV, raising=False)
    first = configure_compile_cache()
    monkeypatch.delenv(CACHE_DIR_ENV)
    assert configure_compile_cache() == first == str(DEFAULT_CACHE_DIR)
    assert DEFAULT_CACHE_DIR == REPO_ROOT / ".jax_cache"
    assert jax.config.jax_compilation_cache_dir == first
    # exported, so a child process (a drill re-exec) lands on the same one
    assert os.environ[CACHE_DIR_ENV] == first
