"""The entry points' persistent compilation cache placement
(launch/compile_cache.py): ``JAX_COMPILATION_CACHE_DIR`` wins untouched,
else a fixed directory in the checkout.  Each case runs in a fresh
interpreter so the global JAX config of the test process stays as it is."""
import os
import subprocess
import sys

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

_PROBE = ("import jax\n"
          "from repro.launch.compile_cache import use_compilation_cache\n"
          "print(use_compilation_cache())\n"
          "print(jax.config.jax_compilation_cache_dir)\n")


def _probe(env_dir):
    env = dict(os.environ, PYTHONPATH=os.path.join(REPO, "src"),
               JAX_PLATFORMS="cpu")
    env.pop("JAX_COMPILATION_CACHE_DIR", None)
    if env_dir is not None:
        env["JAX_COMPILATION_CACHE_DIR"] = env_dir
    r = subprocess.run([sys.executable, "-c", _PROBE], capture_output=True,
                       text=True, env=env, timeout=120)
    assert r.returncode == 0, r.stderr[-2000:]
    return r.stdout.split()


def test_env_cache_dir_is_left_to_jax(tmp_path):
    assert _probe(str(tmp_path)) == [str(tmp_path), str(tmp_path)]


def test_default_cache_dir_is_fixed_in_checkout():
    want = os.path.join(REPO, ".jax_cache")
    assert _probe(None) == [want, want]
