"""Import hygiene for the launch tools (DESIGN.md §12 bugfix sweep).

``repro.launch.dryrun`` used to call ``os.environ.setdefault("XLA_FLAGS",
"--xla_force_host_platform_device_count=512")`` at module import, so any
tool importing it for :func:`resolve_config`/:func:`lower_pair` silently
pinned a 512-device view for its whole process.  The env setup now lives
behind the CLI entry point; these tests pin that imports stay
side-effect-free.  Fresh interpreters (the parent pytest process already
initialised jax), with any inherited XLA_FLAGS scrubbed.
"""

import os
import subprocess
import sys

SRC = os.path.join(os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
                   "src")


def _run(script: str) -> str:
    env = dict(os.environ)
    env.pop("XLA_FLAGS", None)
    out = subprocess.run([sys.executable, "-c", script], capture_output=True,
                         text=True, env=env, timeout=240)
    assert out.returncode == 0, \
        f"STDOUT:\n{out.stdout}\nSTDERR:\n{out.stderr[-3000:]}"
    return out.stdout


def test_dryrun_import_has_no_side_effects():
    _run(f"""
import os, sys
sys.path.insert(0, {SRC!r})
import repro.launch.dryrun as dryrun
assert "XLA_FLAGS" not in os.environ, os.environ["XLA_FLAGS"]
import jax
assert jax.device_count() == 1, jax.device_count()
# the CLI entry is where the sweep's 512-device default comes from
dryrun._force_host_device_count()
assert "512" in os.environ["XLA_FLAGS"]
""")


def test_dryrun_cli_env_respects_caller_flags():
    """An explicit caller-supplied XLA_FLAGS (the CI smokes) must win."""
    _run(f"""
import os, sys
sys.path.insert(0, {SRC!r})
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import repro.launch.dryrun as dryrun
dryrun._force_host_device_count()
assert os.environ["XLA_FLAGS"].endswith("device_count=8")
""")


def test_launch_module_imports_leave_device_view_alone():
    """mesh/train/elastic stay importable without touching device state."""
    _run(f"""
import os, sys
sys.path.insert(0, {SRC!r})
import repro.launch.mesh
import repro.launch.train
import repro.launch.elastic
assert "XLA_FLAGS" not in os.environ
import jax
assert jax.device_count() == 1, jax.device_count()
""")


def _cache_script(env_dir) -> str:
    return f"""
import os, sys
sys.path.insert(0, {SRC!r})
from repro.launch import compile_cache
import jax
got = compile_cache.enable_compile_cache()
assert jax.config.jax_compilation_cache_dir == got, got
want = {env_dir!r}
if want is None:
    assert got == os.path.join(os.path.dirname({SRC!r}), ".jax_cache"), got
else:
    assert got == want, got
    hits = []
    jax.monitoring.register_event_listener(
        lambda e, **_: hits.append(e)
        if e == "/jax/compilation_cache/cache_hits" else None)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.jit(lambda x: x * 3 + 1)(jax.numpy.arange(5.0)).block_until_ready()
    print("HITS", len(hits))
"""


def test_compile_cache_fixed_repo_path_without_env():
    """No ``JAX_COMPILATION_CACHE_DIR``: the cache goes to ``.jax_cache`` at
    the root of the checkout (the helper compiles nothing here)."""
    env = dict(os.environ)
    env.pop("JAX_COMPILATION_CACHE_DIR", None)
    out = subprocess.run([sys.executable, "-c", _cache_script(None)],
                         capture_output=True, text=True, env=env, timeout=240)
    assert out.returncode == 0, out.stderr[-3000:]


def test_compile_cache_env_dir_wins_and_hits_on_second_run(tmp_path):
    """``JAX_COMPILATION_CACHE_DIR`` stands: entries land there, and a second
    identical process reads them back."""
    env = dict(os.environ, JAX_COMPILATION_CACHE_DIR=str(tmp_path))
    env.pop("XLA_FLAGS", None)
    runs = [subprocess.run([sys.executable, "-c", _cache_script(str(tmp_path))],
                           capture_output=True, text=True, env=env,
                           timeout=240) for _ in range(2)]
    for r in runs:
        assert r.returncode == 0, r.stderr[-3000:]
    hits = [int(r.stdout.split("HITS")[1]) for r in runs]
    assert hits[0] == 0 and hits[1] > 0, hits
    assert any(tmp_path.iterdir())
