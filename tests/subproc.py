"""Shared subprocess harness for forced-host-device distributed tests.

The main pytest process must keep the real single-device CPU view, so every
test needing an N-device mesh runs its body in a fresh interpreter with
``XLA_FLAGS=--xla_force_host_platform_device_count=N`` (and any inherited
flag scrubbed from the parent env).  Used by tests/test_distributed.py and
tests/test_group_average_fused.py.
"""

import os
import subprocess
import sys
import textwrap

SRC = os.path.join(os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
                   "src")


def run_sub(body: str, devices: int = 8, timeout: int = 420,
            preamble: str = "") -> str:
    """Run dedented ``body`` on ``devices`` forced host devices.

    The script sees jax/jnp/np, PartitionSpec P, NamedSharding,
    ``repro.compat`` and the mesh constructor ``make_mesh`` pre-imported;
    ``preamble`` (also dedented) can add test-module-specific helpers
    before the body runs.
    """
    script = textwrap.dedent(f"""
        import os
        os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count={devices}"
        import sys
        sys.path.insert(0, {SRC!r})
        import jax, jax.numpy as jnp
        import numpy as np
        from jax.sharding import PartitionSpec as P, NamedSharding
        from repro import compat
        from repro.launch.mesh import make_mesh
    """) + textwrap.dedent(preamble) + textwrap.dedent(body)
    env = dict(os.environ)
    env.pop("XLA_FLAGS", None)
    out = subprocess.run([sys.executable, "-c", script], capture_output=True,
                         text=True, env=env, timeout=timeout)
    assert out.returncode == 0, f"STDOUT:\n{out.stdout}\nSTDERR:\n{out.stderr[-3000:]}"
    return out.stdout
