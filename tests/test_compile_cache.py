"""The entry points' persistent compilation cache (launch/compile_cache.py).

Each case runs in a fresh interpreter: turning the cache on is a global
JAX setting, and a second run must find what the first one wrote."""
import json
import os
import pathlib
import subprocess
import sys
import textwrap

from repro.launch import compile_cache

ROOT = pathlib.Path(__file__).resolve().parents[1]

_SCRIPT = textwrap.dedent("""
    import json, pathlib, sys
    import jax, jax.numpy as jnp
    from repro.launch import compile_cache
    if len(sys.argv) > 1:
        compile_cache.CACHE_DIR = pathlib.Path(sys.argv[1])
    hits = []
    jax.monitoring.register_event_listener(
        lambda event, **kw: hits.append(event)
        if event == "/jax/compilation_cache/cache_hits" else None)
    where = compile_cache.enable_compile_cache()
    jax.jit(lambda x: jnp.sin(x) * 2 + 1)(jnp.arange(8.0)).block_until_ready()
    print(json.dumps({"dir": where, "hits": len(hits)}))
""")


def _run(*args, env_dir=None):
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               PYTHONPATH=str(ROOT / "src"),
               # cache even this one tiny program
               JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS="0",
               JAX_PERSISTENT_CACHE_MIN_ENTRY_SIZE_BYTES="0")
    env.pop("JAX_COMPILATION_CACHE_DIR", None)
    if env_dir is not None:
        env["JAX_COMPILATION_CACHE_DIR"] = str(env_dir)
    out = subprocess.run([sys.executable, "-c", _SCRIPT, *map(str, args)],
                         env=env, capture_output=True, text=True, timeout=300)
    assert out.returncode == 0, out.stderr
    return json.loads(out.stdout.strip().splitlines()[-1])


def test_default_dir_is_fixed_inside_the_checkout():
    assert compile_cache.CACHE_DIR == ROOT / ".jax_cache"
    ignored = (ROOT / ".gitignore").read_text().split()
    assert ".jax_cache/" in ignored


def test_second_run_hits_the_default_dir(tmp_path):
    cache = tmp_path / "cache"
    first = _run(cache)
    assert first == {"dir": str(cache), "hits": 0}
    entries = sorted(p.name for p in cache.iterdir())
    assert entries
    second = _run(cache)
    assert second["hits"] >= 1
    assert sorted(p.name for p in cache.iterdir()) == entries


def test_environment_dir_stays_in_charge(tmp_path):
    env_dir, default = tmp_path / "env", tmp_path / "default"
    first = _run(default, env_dir=env_dir)
    assert first == {"dir": str(env_dir), "hits": 0}
    assert any(env_dir.iterdir()) and not default.exists()
    assert _run(default, env_dir=env_dir)["hits"] >= 1
