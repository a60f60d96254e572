import os
import subprocess
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture(autouse=True)
def _isolate_autotune_cache(tmp_path, monkeypatch):
    """Keep every test's block-autotuner resolution away from the user's
    persistent ~/.cache JSON (kernel paths consult it implicitly)."""
    from repro.kernels import autotune
    monkeypatch.setenv(autotune.ENV_CACHE, str(tmp_path / "autotune.json"))
    autotune.clear_memory_cache()
    yield
    autotune.clear_memory_cache()


def run_with_devices(code: str, n_devices: int = 8, timeout: int = 600):
    """Run a python snippet in a subprocess with N fake host devices.

    Multi-device tests must not pollute this process's jax device state
    (smoke tests and benches see 1 device, per the assignment).  The child
    is pinned to the CPU: an accelerator belongs to one process, and this
    one may already hold it.
    """
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env["XLA_FLAGS"] = f"--xla_force_host_platform_device_count={n_devices}"
    env["PYTHONPATH"] = os.path.join(REPO, "src")
    proc = subprocess.run([sys.executable, "-c", code], env=env,
                          capture_output=True, text=True, timeout=timeout)
    if proc.returncode != 0:
        raise AssertionError(
            f"subprocess failed:\nSTDOUT:\n{proc.stdout}\nSTDERR:\n{proc.stderr}")
    return proc.stdout
