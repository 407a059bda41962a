"""``chip_smoke.py`` on the CPU: the rehearsal runs end to end at a tiny
size and reports the CPU honestly; without ``--rehearse`` the script
refuses to run anywhere but a TPU and prints no result."""

import json
import os
import subprocess
import sys
from pathlib import Path

import pytest

SMOKE = Path(__file__).resolve().parents[1] / "chip_smoke.py"


def _run(*args, timeout=600):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    return subprocess.run([sys.executable, str(SMOKE), *args], env=env,
                          capture_output=True, text=True, timeout=timeout)


@pytest.mark.parametrize("four_chips", [False, True])
def test_rehearsal_runs_end_to_end(four_chips):
    args = ["--rehearse"] + (["--four-chips"] if four_chips else [])
    p = _run(*args)
    assert p.returncode == 0, p.stdout[-3000:] + p.stderr[-3000:]
    last = json.loads(p.stdout.strip().splitlines()[-1])
    assert last == {"ok": True, "device": {
        "platform": "cpu", "kind": "cpu", "count": 4 if four_chips else 1}}
    assert "check_delta_bound(delta=0.2, alpha=1.0) -> None" in p.stdout
    if four_chips:
        assert "matches host_reference_merge on 32/32" in p.stdout
    else:
        assert "queries with different ids 0" in p.stdout


def test_without_rehearse_fails_off_tpu():
    p = _run(timeout=120)
    assert p.returncode != 0
    assert '"ok"' not in p.stdout
    assert "not a TPU" in p.stderr


def test_compile_cache_placement(monkeypatch, tmp_path):
    """``JAX_COMPILATION_CACHE_DIR`` wins and nothing else is set in code;
    without it the cache sits at one fixed path in the checkout."""
    import jax

    from repro.launch.cache import CHECKOUT_CACHE_DIR, use_compile_cache

    before = jax.config.jax_compilation_cache_dir
    try:
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
        assert use_compile_cache() == str(tmp_path)
        assert jax.config.jax_compilation_cache_dir == before
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR")
        assert use_compile_cache() == str(CHECKOUT_CACHE_DIR)
        assert jax.config.jax_compilation_cache_dir == str(CHECKOUT_CACHE_DIR)
        assert CHECKOUT_CACHE_DIR == SMOKE.parent / ".jax_cache"
    finally:
        jax.config.update("jax_compilation_cache_dir", before)
