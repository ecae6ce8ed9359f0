"""The one compile-cache helper (fisco_bcos_tpu/utils/jaxenv.py): placeable
from outside through ``JAX_COMPILATION_CACHE_DIR``, otherwise exactly
``<checkout>/.jax_cache`` — and configured nowhere else in the tree."""

from __future__ import annotations

import os
import re
import subprocess
import sys

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

# run in a fresh interpreter: the cache is initialised once per process and
# the test session has long since compiled something
_PROBE = """
import json, os, sys
import jax
updates = []
_orig = jax.config.update
jax.config.update = lambda k, v: (updates.append(k), _orig(k, v))[1]
from fisco_bcos_tpu.utils import jaxenv
default_dir = jaxenv.DEFAULT_CACHE_DIR
before = set(os.listdir(default_dir)) if os.path.isdir(default_dir) else set()
in_effect = jaxenv.configure_compile_cache()
import jax.numpy as jnp
jax.jit(lambda x: (x * 7 + int(sys.argv[1])).sum())(jnp.arange(64.0)).block_until_ready()
after = set(os.listdir(default_dir)) if os.path.isdir(default_dir) else set()
print(json.dumps({
    "updates": updates, "in_effect": in_effect, "default_dir": default_dir,
    "new_in_default": sorted(after - before),
    "config": jax.config.jax_compilation_cache_dir,
}))
"""


def _probe(env_dir: str | None, salt: int) -> dict:
    import json

    env = {k: v for k, v in os.environ.items() if k != "JAX_COMPILATION_CACHE_DIR"}
    env["PYTHONPATH"] = REPO
    env["JAX_PLATFORMS"] = "cpu"
    if env_dir is not None:
        env["JAX_COMPILATION_CACHE_DIR"] = env_dir
    res = subprocess.run(
        [sys.executable, "-c", _PROBE, str(salt)], env=env, cwd=REPO,
        capture_output=True, text=True, timeout=300,
    )
    assert res.returncode == 0, res.stderr[-2000:]
    return json.loads(res.stdout.strip().splitlines()[-1])


def test_env_set_means_no_directory_set_from_code(tmp_path):
    outside = str(tmp_path / "placed-from-outside")
    doc = _probe(outside, salt=os.getpid())
    assert "jax_compilation_cache_dir" not in doc["updates"]
    assert doc["in_effect"] == doc["config"] == outside
    assert os.listdir(outside), "the program's entry did not land in the env dir"
    assert doc["new_in_default"] == [], "something was written to <checkout>/.jax_cache"


def test_env_unset_means_checkout_dot_jax_cache():
    doc = _probe(None, salt=1)
    assert doc["updates"].count("jax_compilation_cache_dir") == 1
    assert doc["in_effect"] == doc["config"] == os.path.join(REPO, ".jax_cache")
    assert doc["default_dir"] == os.path.join(REPO, ".jax_cache")


def _sources():
    skip = {".git", ".jax_cache", "chiprun_out", "__pycache__", ".pytest_cache"}
    for root, dirs, files in os.walk(REPO):
        dirs[:] = [d for d in dirs if d not in skip]
        for name in files:
            if name.endswith((".py", ".sh")):
                yield os.path.join(root, name)


def test_cache_dir_is_configured_in_exactly_one_place():
    set_from_code = re.compile(r"""update\(\s*["']jax_compilation_cache_dir""")
    # reading the variable is fine; assigning or defaulting it is not
    set_in_env = re.compile(
        r"""setdefault\(\s*["']JAX_COMPILATION_CACHE_DIR"""
        r"""|environ\[\s*["']JAX_COMPILATION_CACHE_DIR["']\s*\]\s*=[^=]"""
    )
    code_sites, env_sites = [], []
    for path in _sources():
        rel = os.path.relpath(path, REPO)
        with open(path, errors="replace") as f:
            text = f.read()
        code_sites += [rel] * len(set_from_code.findall(text))
        if not rel.startswith("tests" + os.sep):
            env_sites += [rel] * len(set_in_env.findall(text))
    assert code_sites == [os.path.join("fisco_bcos_tpu", "utils", "jaxenv.py")]
    assert env_sites == []
