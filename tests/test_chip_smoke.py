"""chip_smoke.py refuses to pass off the chip; the compile cache's place."""

import os
import shutil
import subprocess
import sys

import jax

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

from benchmarks.common import use_compile_cache  # noqa: E402


def _run_smoke(script_dir):
    env = dict(os.environ, JAX_PLATFORMS="cpu")  # never the chip
    return subprocess.run([sys.executable, "chip_smoke.py"], cwd=script_dir,
                          env=env, capture_output=True, text=True,
                          timeout=300)


def _assert_refused(out):
    assert out.returncode != 0
    assert '"ok"' not in out.stdout
    assert "no TPU" in out.stderr and "'cpu'" in out.stderr


def test_chip_smoke_fails_on_cpu_and_names_the_missing_chip():
    _assert_refused(_run_smoke(ROOT))


ALONE = """
import sys
import jax
import chip_smoke

class Chip:
    platform, device_kind = "tpu", "a stand-in"

jax.devices = lambda *a: [Chip()]   # get past the platform check
sys.exit(chip_smoke.main([]))
"""


def test_chip_smoke_alone_fails(tmp_path):
    # even where a chip is found, the script without the repository beside
    # it stops at the import and prints no result
    shutil.copy(os.path.join(ROOT, "chip_smoke.py"), tmp_path)
    env = {k: v for k, v in os.environ.items() if k != "PYTHONPATH"}
    env["JAX_PLATFORMS"] = "cpu"
    out = subprocess.run([sys.executable, "-c", ALONE], cwd=tmp_path,
                         env=env, capture_output=True, text=True, timeout=300)
    assert out.returncode == 2, out.stderr[-3000:]
    assert '"ok"' not in out.stdout
    assert "code is not beside this script" in out.stderr


def test_compile_cache_goes_where_the_env_says(monkeypatch):
    before = jax.config.jax_compilation_cache_dir
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "/cache/from/outside")
    assert use_compile_cache() == "/cache/from/outside"
    assert jax.config.jax_compilation_cache_dir == before  # sets no other
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR")
    try:
        path = use_compile_cache()
        assert path == os.path.join(ROOT, ".jax_cache")
        assert jax.config.jax_compilation_cache_dir == path
    finally:
        jax.config.update("jax_compilation_cache_dir", before)
    with open(os.path.join(ROOT, ".gitignore")) as f:
        assert ".jax_cache/" in f.read().split()

