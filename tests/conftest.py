# NOTE: XLA_FLAGS / device-count overrides are intentionally NOT set here
# (the dry-run sets 512 host devices itself; unit tests must see 1 device).
import os
import subprocess
import sys
import textwrap

import pytest


def run_with_devices(code: str, n_devices: int = 8,
                     timeout: int = 420) -> str:
    """Run ``code`` in a subprocess with N host platform devices.

    Multi-device tests need XLA_FLAGS before jax's first init, which
    cannot happen inside an already-initialized test process.
    Raises on failure with the subprocess output in the message.
    """
    env = dict(os.environ)
    # host devices only: the child must never reach for an accelerator
    # that this process may already hold
    env["JAX_PLATFORMS"] = "cpu"
    env["XLA_FLAGS"] = (
        f"--xla_force_host_platform_device_count={n_devices}")
    env["PYTHONPATH"] = os.path.join(
        os.path.dirname(os.path.dirname(os.path.abspath(__file__))), "src")
    proc = subprocess.run(
        [sys.executable, "-c", textwrap.dedent(code)],
        capture_output=True, text=True, timeout=timeout, env=env)
    if proc.returncode != 0:
        raise AssertionError(
            f"subprocess failed ({proc.returncode}):\n"
            f"stdout:\n{proc.stdout}\nstderr:\n{proc.stderr[-4000:]}")
    return proc.stdout


@pytest.fixture(scope="session")
def devices8():
    return run_with_devices
