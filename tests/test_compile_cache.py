"""Placement of JAX's persistent compilation cache by the entry points'
helper: ``JAX_COMPILATION_CACHE_DIR`` when set, else ``<repo>/.jax_cache``."""
import os
import subprocess
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]

SCRIPT = """
import sys
sys.path.insert(0, sys.argv[1])
import jax, jax.numpy as jnp
from repro.launch.compile_cache import enable_compile_cache
path = enable_compile_cache()
assert jax.config.jax_compilation_cache_dir == path
if sys.argv[2] == "compile":
    jax.jit(lambda x: jnp.sin(x) * 3)(jnp.ones(8)).block_until_ready()
print("CACHE", path)
"""


def _run(env_dir, action):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("JAX_COMPILATION_CACHE_DIR", None)
    if env_dir is not None:
        env["JAX_COMPILATION_CACHE_DIR"] = str(env_dir)
    r = subprocess.run([sys.executable, "-c", SCRIPT, str(ROOT / "src"),
                        action], capture_output=True, text=True,
                       timeout=300, env=env)
    line = [ln for ln in r.stdout.splitlines() if ln.startswith("CACHE ")]
    assert line, r.stdout + r.stderr
    return line[0][len("CACHE "):]


def test_env_dir_is_the_only_place_written(tmp_path):
    repo_cache = ROOT / ".jax_cache"
    existed = repo_cache.exists()
    before = set(os.listdir(repo_cache)) if existed else set()
    target = tmp_path / "cc"
    assert _run(target, "compile") == str(target)
    assert any(target.iterdir())
    if existed:
        assert set(os.listdir(repo_cache)) == before
    else:
        assert not repo_cache.exists()


def test_default_is_the_fixed_repo_path():
    assert _run(None, "none") == str(ROOT / ".jax_cache")
