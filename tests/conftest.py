import functools
import os
import subprocess
import sys

import numpy as np
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
SRC = os.path.join(REPO, "src")


@pytest.fixture
def rng():
    return np.random.default_rng(0)


def _device_env(n_devices: int) -> dict:
    env = dict(os.environ)
    env["XLA_FLAGS"] = f"--xla_force_host_platform_device_count={n_devices}"
    env["PYTHONPATH"] = SRC + os.pathsep + env.get("PYTHONPATH", "")
    return env


@functools.lru_cache(maxsize=None)
def _mesh_unavailable_reason(n_devices: int) -> str | None:
    """None when the host can build the mesh these tests need, else why not.

    Probed once per device count in a subprocess: the host may expose fewer
    devices than requested, and then the multi-device tests should skip,
    not fail.
    """
    probe = (
        "import jax\n"
        f"assert jax.device_count() >= {n_devices}, "
        f"'only ' + str(jax.device_count()) + ' of {n_devices} host devices'\n"
        f"jax.make_mesh(({n_devices},), ('probe',), "
        "axis_types=(jax.sharding.AxisType.Auto,))\n"
    )
    try:
        res = subprocess.run([sys.executable, "-c", probe],
                             env=_device_env(n_devices), capture_output=True,
                             text=True, timeout=240)
    except subprocess.TimeoutExpired:
        return "mesh probe timed out after 240s"
    if res.returncode == 0:
        return None
    tail = (res.stderr or res.stdout).strip().splitlines()
    return tail[-1] if tail else "mesh probe subprocess failed"


def run_in_devices(code: str, n_devices: int = 8, timeout: int = 480) -> str:
    """Run a python snippet in a subprocess with N host platform devices.

    Smoke tests must see 1 device (no global XLA_FLAGS), so multi-device
    tests spawn their own interpreter with the flag set pre-import.  Skips
    (rather than fails) when the host cannot provide the requested mesh.
    """
    reason = _mesh_unavailable_reason(n_devices)
    if reason is not None:
        pytest.skip(f"cannot run a {n_devices}-device host mesh: {reason}")
    res = subprocess.run([sys.executable, "-c", code],
                         env=_device_env(n_devices), capture_output=True,
                         text=True, timeout=timeout)
    assert res.returncode == 0, f"subprocess failed:\n{res.stdout}\n{res.stderr}"
    return res.stdout
