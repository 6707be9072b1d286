"""chip_smoke.py without a chip: it refuses the CPU, its phase functions
walk their whole control flow at gpt_tiny size on the CPU mesh, and the
compile-cache resolver puts every cache under one root."""
import os
import subprocess
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

import chip_smoke  # noqa: E402


def test_refuses_cpu_and_prints_no_result():
    r = subprocess.run([sys.executable, os.path.join(REPO, "chip_smoke.py")],
                       cwd=REPO, env=dict(os.environ, JAX_PLATFORMS="cpu"),
                       capture_output=True, text=True, timeout=120)
    assert r.returncode != 0
    assert "'cpu'" in r.stderr and "not a TPU" in r.stderr
    assert '"ok"' not in r.stdout


@pytest.fixture
def cache_root(tmp_path, monkeypatch):
    """The phases persist executables; keep them out of the checkout."""
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path / "cache"))
    return tmp_path / "cache"


def test_phases_pass_their_own_checks_at_tiny_size(cache_root):
    """Same functions, same checks as on the chip — minus the Mosaic
    kernels, which only a TPU lowering contains."""
    report, counter = chip_smoke.Report(), chip_smoke.CompileCounter()
    tiny = dict(model="gpt_tiny", batch=4, seq_len=128, fused=2,
                expect_flash=False)
    one = chip_smoke.trainer_phase(report, counter, **tiny)
    assert len(one["losses"]) == 5
    chip_smoke.server_phase(report, counter, model="gpt_tiny")
    chip_smoke.four_chip_phase(report, counter, one, large=1 << 12, **tiny)
    assert report.failed == []
    # the server phase's executables went under the one root
    assert os.listdir(cache_root / "paddle_tpu_aot")


def test_a_failed_check_is_remembered_not_raised(capsys):
    report = chip_smoke.Report()
    assert report.check("fine", True)
    assert not report.check("broken", False, "why")
    assert report.failed == ["broken"]
    assert "[FAIL] broken: why" in capsys.readouterr().out


def test_cache_resolver_one_root_inside_env_or_checkout(tmp_path,
                                                        monkeypatch):
    """$JAX_COMPILATION_CACHE_DIR when set (jax reads it itself; nothing is
    set in code), else <checkout>/.jax_cache — never $HOME, a temp name, a
    pid or a time."""
    import jax
    from paddle_tpu.jit import aot

    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path / "x"))
    before = jax.config.jax_compilation_cache_dir
    assert aot.enable_compile_cache() == str(tmp_path / "x")
    assert jax.config.jax_compilation_cache_dir == before   # untouched
    assert aot.cache_dir() == str(tmp_path / "x" / "paddle_tpu_aot")
    assert aot.default_cache().root == aot.cache_dir()

    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR")
    fixed = os.path.join(REPO, ".jax_cache")
    assert aot.compile_cache_root() == fixed
    assert aot.cache_dir() == os.path.join(fixed, "paddle_tpu_aot")
    assert aot.default_cache().root == aot.cache_dir()   # rebuilt
    try:
        assert aot.enable_compile_cache() == fixed
        assert jax.config.jax_compilation_cache_dir == fixed
    finally:
        jax.config.update("jax_compilation_cache_dir", before)
    with open(os.path.join(REPO, ".gitignore")) as f:
        assert ".jax_cache/" in f.read().split()
