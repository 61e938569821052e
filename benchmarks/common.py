"""Shared helpers for the benchmark harness."""
import json
import os
import subprocess
import sys
import time

SRC = os.path.join(os.path.dirname(__file__), "..", "src")
if SRC not in sys.path:
    sys.path.insert(0, SRC)


class Timer:
    def __enter__(self):
        self.t0 = time.time()
        return self

    def __exit__(self, *a):
        self.seconds = time.time() - self.t0


def run_subprocess_devices(code: str, n_devices: int, timeout=560) -> str:
    """Run a python snippet with N host-emulated devices; returns stdout.

    The child is pinned to the CPU backend: host-emulated devices are CPU
    devices, and on a TPU host an unpinned child would claim the chip."""
    env = dict(os.environ)
    env["XLA_FLAGS"] = f"--xla_force_host_platform_device_count={n_devices}"
    env["JAX_PLATFORMS"] = "cpu"
    env["PYTHONPATH"] = os.path.abspath(SRC)
    res = subprocess.run([sys.executable, "-c", code], env=env,
                         capture_output=True, text=True, timeout=timeout)
    if res.returncode != 0:
        raise RuntimeError(f"subprocess failed:\n{res.stderr[-2000:]}")
    return res.stdout


def emit(rows, header=("name", "us_per_call", "derived")):
    print(",".join(header))
    for r in rows:
        print(",".join(str(x) for x in r))
