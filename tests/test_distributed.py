"""Distributed Jigsaw correctness, run in subprocesses (each with 16
host-emulated devices so XLA_FLAGS never leaks into other tests)."""
import os
import subprocess
import sys

import pytest

HERE = os.path.dirname(__file__)
SCRIPT = os.path.join(HERE, "dist_scenarios.py")

SCENARIOS = [
    "jigsaw_1d",
    "jigsaw_1d_fsdp",
    "jigsaw_2d",
    # ring_chunked_parity runs via tests/test_kernel_parity.py (the
    # kernels CI job needs it there; listing it here too would double
    # its interpret-mode cost in tier-1)
    "ring_collectives",
    "weathermixer_schemes",
    "transformer_1d",
    "train_step_mesh",
    "input_pipeline",
    "engine_pipeline",
    "zero1_engine",
    # ckpt_sharded_reshard runs via tests/test_checkpoint.py (the
    # checkpoint CI job needs it there; listing it here too would
    # double its cost in tier-1)
    "resume_exact",
    "precision_bf16",
    # preempt_resume_exact + elastic_reshard_resume run via
    # tests/test_resilience.py (the resilience CI job needs them there;
    # listing them here too would double their cost in tier-1)
    # serving_restore runs via tests/test_serve.py (the serve CI job
    # needs it there; same double-cost rule)
]


@pytest.mark.parametrize("scenario", SCENARIOS)
def test_scenario(scenario):
    env = dict(os.environ)
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=16"
    env["JAX_PLATFORMS"] = "cpu"
    res = subprocess.run(
        [sys.executable, SCRIPT, scenario], env=env,
        capture_output=True, text=True, timeout=600)
    assert res.returncode == 0 and "ALL-OK" in res.stdout, (
        f"\nstdout:\n{res.stdout[-3000:]}\nstderr:\n{res.stderr[-3000:]}")
