"""Multi-device distribution tests via subprocess (8 fake host devices).

A subprocess is mandatory: jax locks the device count at first init, and
the main pytest process must keep seeing ONE device."""
import os
import subprocess
import sys

import pytest


@pytest.mark.slow
def test_multidevice_suite():
    script = os.path.join(os.path.dirname(__file__), "_multidev_checks.py")
    env = dict(os.environ)
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    env["JAX_PLATFORMS"] = "cpu"
    proc = subprocess.run([sys.executable, script], env=env,
                          capture_output=True, text=True, timeout=900)
    sys.stdout.write(proc.stdout)
    sys.stderr.write(proc.stderr)
    assert proc.returncode == 0, proc.stderr[-2000:]
    assert "MULTIDEV ALL OK" in proc.stdout
