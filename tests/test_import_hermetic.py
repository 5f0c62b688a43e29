"""Importing mxnet_tpu must never initialize a JAX backend.

Round-1 regression: ``ops/detection.py`` had a module-level
``jnp.float32(-1.0)`` that dispatched an eager JAX primitive at import time,
forcing TPU-backend initialization during ``import mxnet_tpu``.  A chip
belongs to one process, so a parent that merely imports the package (a
launcher) would take the chip from the child that needs it.  Import must
be hermetic: zero device dispatch, zero backend init.
"""
import os
import subprocess
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

_CHECK = """
import sys
sys.path.insert(0, @ROOT@)
from jax._src import xla_bridge
import mxnet_tpu
assert not xla_bridge.backends_are_initialized(), (
    "import mxnet_tpu initialized JAX backend(s): %r" %
    list(xla_bridge._backends))
print("HERMETIC")
""".replace("@ROOT@", repr(ROOT))


def test_import_is_hermetic():
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    out = subprocess.run(
        [sys.executable, "-c", _CHECK], env=env, capture_output=True,
        text=True, timeout=120,
        cwd=os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
    assert out.returncode == 0, out.stderr
    assert "HERMETIC" in out.stdout
