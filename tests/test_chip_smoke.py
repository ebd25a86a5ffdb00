"""chip_smoke.py contract, as far as a machine without a chip can hold it.

The driver runs `python3 chip_smoke.py` on the TPU machine; here the same
script's control flow is rehearsed on the CPU (`--tiny`), and the ways it
must FAIL are checked: no accelerator, and no checkout around it. The
compile-cache function the smoke's children share is checked alongside.
"""

import json
import os
import shutil
import subprocess
import sys
import time

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
SMOKE = os.path.join(REPO, "chip_smoke.py")


def _env(**extra):
    env = {k: v for k, v in os.environ.items()
           if k not in ("JAX_ENABLE_COMPILATION_CACHE",
                        "JAX_COMPILATION_CACHE_DIR")}
    env.update(extra)
    return env


def test_tiny_rehearsal_passes_end_to_end(tmp_path):
    cache = tmp_path / "cache"
    p = subprocess.run(
        [sys.executable, SMOKE, "--tiny", "--out", str(tmp_path / "out")],
        capture_output=True, text=True, timeout=300,
        env=_env(JAX_COMPILATION_CACHE_DIR=str(cache)))
    assert p.returncode == 0, p.stderr[-3000:]
    lines = [ln for ln in p.stdout.splitlines() if ln.strip()]
    # the report, then LAST the verdict with exactly these keys
    assert len(lines) == 2, lines
    doc = json.loads(lines[0])
    assert json.loads(lines[-1]) == {
        "ok": True, "device": {"platform": "cpu", "kind": "cpu",
                               "count": doc["n_devices"]}}
    assert doc["platform"] == "cpu" and doc["tiny"] is True
    assert doc["train"]["status"] == "COMPLETED"
    assert "platform=cpu" in doc["train"]["mesh"]
    assert doc["queries"]["answered"] == 72
    assert all(k["ok"] and k["interpret"] for k in doc["kernels"])
    # the children compiled into the directory the environment named
    assert doc["compile_cache"]["dir"] == str(cache)
    assert doc["compile_cache"]["entries_after"] > 0
    assert doc["claim"] is None
    # the report is on disk too; the scratch store is gone, the logs stay
    out = tmp_path / "out"
    assert json.loads((out / "chip_smoke.json").read_text()) == doc
    assert not (out / "storage").exists()
    assert (out / "train.log").exists()


def test_without_tiny_and_without_a_tpu_fails_in_seconds(tmp_path):
    t0 = time.time()
    p = subprocess.run(
        [sys.executable, SMOKE, "--out", str(tmp_path / "out")],
        capture_output=True, text=True, timeout=120,
        env=_env(JAX_PLATFORMS="cpu"))
    assert p.returncode != 0
    assert time.time() - t0 < 60
    assert p.stdout.strip() == ""            # no result line
    assert "Unable to initialize backend 'tpu'" in p.stderr


def test_alone_in_a_directory_fails(tmp_path):
    shutil.copy(SMOKE, tmp_path / "chip_smoke.py")
    p = subprocess.run(
        [sys.executable, str(tmp_path / "chip_smoke.py"), "--tiny"],
        capture_output=True, text=True, timeout=60, cwd=tmp_path,
        env=_env())
    assert p.returncode != 0
    assert p.stdout.strip() == ""
    assert "no predictionio_tpu package" in p.stderr


_CACHE_SNIPPET = """
import sys
sys.path.insert(0, {repo!r})
{pre}
from predictionio_tpu.utils.device import enable_compile_cache
d = enable_compile_cache()
import jax
print(d)
print(jax.config.jax_compilation_cache_dir)
print(jax.config.jax_persistent_cache_min_compile_time_secs)
"""


def _cache_dirs(tmp_path, pre="", **extra):
    p = subprocess.run(
        [sys.executable, "-c", _CACHE_SNIPPET.format(repo=REPO, pre=pre)],
        capture_output=True, text=True, timeout=120, cwd=tmp_path,
        env=_env(JAX_PLATFORMS="cpu", **extra))
    assert p.returncode == 0, p.stderr[-2000:]
    return p.stdout.split()


def test_compile_cache_leaves_an_environment_directory_alone(tmp_path):
    named = str(tmp_path / "named-by-env")
    for pre in ("", "import jax"):      # before and after jax is imported
        returned, configured, min_secs = _cache_dirs(
            tmp_path, pre=pre, JAX_COMPILATION_CACHE_DIR=named)
        assert returned == named
        assert configured == named      # JAX's own reading of the env
        assert float(min_secs) == 0.0


def test_compile_cache_default_is_one_fixed_path_in_the_checkout(tmp_path):
    other = tmp_path / "elsewhere"
    other.mkdir()
    a = _cache_dirs(tmp_path)
    b = _cache_dirs(other, pre="import jax")
    assert a[:2] == b[:2]
    assert a[0] == a[1] == os.path.join(REPO, ".jax_cache")
    assert float(a[2]) == float(b[2]) == 0.0
