"""``chip_smoke.py`` on the CPU: its run function drives the DLRM-RMC1
smoke config through the live serving path with the platform check
steered from the test, and its entry point refuses any platform but the
TPU."""
import importlib.util
import os
import subprocess
import sys

import numpy as np
import pytest

from repro import configs

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
SMOKE_CFG = configs.get("dlrm-rmc1").smoke_config


@pytest.fixture(scope="module")
def chip_smoke():
    spec = importlib.util.spec_from_file_location(
        "chip_smoke", os.path.join(REPO, "chip_smoke.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def test_run_serves_smoke_config_with_no_compile_in_window(chip_smoke):
    lines = []
    device = chip_smoke.run(SMOKE_CFG, platform="cpu", n_queries=80,
                            log=lines.append)
    assert device == {"platform": "cpu", "kind": "cpu", "count": 1}
    out = "\n".join(lines)
    assert "served 80/80 queries" in out
    assert "errors=0 dropped=0 feed_errors=0 compiles_in_window=0" in out
    assert "max_abs_err/scale=" in out


def test_run_fails_on_compiles_inside_the_window(chip_smoke, monkeypatch):
    """Device-array batches make the runtime slice and pad on the device,
    which compiles per request shape inside the window: the run must
    fail on that, not report it."""
    import jax.numpy as jnp
    host_batches = chip_smoke.recsys_model

    def device_batches(cfg, **kw):
        apply_fn, make_batch, params = host_batches(cfg, **kw)
        return (apply_fn,
                lambda size, mid: {k: jnp.asarray(v)
                                   for k, v in make_batch(size, mid).items()},
                params)

    monkeypatch.setattr(chip_smoke, "recsys_model", device_batches)
    with pytest.raises(chip_smoke.SmokeFailure,
                       match="compiles inside the served window"):
        chip_smoke.run(SMOKE_CFG, platform="cpu", n_queries=40,
                       log=lambda line: None)


def test_served_batches_are_host_numpy(chip_smoke):
    _, make_batch, _ = chip_smoke.recsys_model(SMOKE_CFG, max_rows=16)
    batch = make_batch(5, -1)
    assert all(isinstance(v, np.ndarray) and v.shape[0] == 5
               for v in batch.values())
    with pytest.raises(ValueError, match="exceeds"):
        make_batch(17, -1)


def test_run_refuses_a_platform_it_was_not_given(chip_smoke):
    with pytest.raises(chip_smoke.SmokeFailure, match="needs 'tpu'"):
        chip_smoke.run(SMOKE_CFG, log=lambda line: None)


def test_entry_point_refuses_non_tpu_platform():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    res = subprocess.run([sys.executable, os.path.join(REPO, "chip_smoke.py")],
                         env=env, capture_output=True, text=True, timeout=300)
    assert res.returncode != 0
    assert '"ok"' not in res.stdout
    assert "needs 'tpu'" in res.stderr


@pytest.mark.parametrize("env_dir", [None, "/elsewhere/cache"])
def test_compile_cache_is_the_env_dir_or_the_checkout_dir(env_dir):
    env = {k: v for k, v in os.environ.items()
           if k != "JAX_COMPILATION_CACHE_DIR"}
    if env_dir:
        env["JAX_COMPILATION_CACHE_DIR"] = env_dir
    env["PYTHONPATH"] = os.path.join(REPO, "src")
    code = ("import jax\n"
            "from repro.utils import use_compile_cache\n"
            "use_compile_cache()\n"
            "print(jax.config.jax_compilation_cache_dir)\n")
    res = subprocess.run([sys.executable, "-c", code], env=env,
                         capture_output=True, text=True, timeout=120)
    assert res.returncode == 0, res.stderr
    assert res.stdout.strip() == (env_dir or os.path.join(REPO, ".jax_cache"))
