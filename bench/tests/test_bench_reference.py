"""The plain float32 reference against the program's WeatherMixer, on
seeded weights at a small size on the CPU."""
import numpy as np
import pytest

import _paths  # noqa: F401
import jax
import jax.numpy as jnp

from harness import synth, weights
from reference import weathermixer as wm


@pytest.fixture(scope="module")
def setup():
    from harness.spec import model_config
    from repro.launch import shapes as SH
    cfg = dict(_paths.TINY)
    mcfg = model_config(cfg)
    jcfg = SH.jigsaw_for(mcfg)
    params = weights.make(3, cfg, jnp.float32)
    params["blend"] = jnp.linspace(-1.0, 1.0, cfg["channels"])
    traffic = {"n_modes": 8, "horizon": 1, "dt_phase": 0.35, "noise": 0.02}
    batch = synth.host_batch(5, 0, cfg, traffic, 2)
    return cfg, mcfg, jcfg, params, batch


def test_forward_matches_program(setup):
    from repro.models import weathermixer as prog
    cfg, mcfg, jcfg, params, batch = setup
    with jax.default_matmul_precision("highest"):
        got, _ = prog.apply(params, {"fields": jnp.asarray(batch["fields"])},
                            mcfg, jcfg)
        want = [wm.forward(params, batch["fields"][i], cfg) for i in range(2)]
    np.testing.assert_allclose(np.asarray(got), np.stack(want),
                               rtol=2e-5, atol=2e-5)


def test_loss_and_grads_match_program(setup):
    from repro.train.step import loss_fn
    cfg, mcfg, jcfg, params, batch = setup
    b = {k: jnp.asarray(v) for k, v in batch.items()}
    with jax.default_matmul_precision("highest"):
        (loss, _), grads = jax.value_and_grad(loss_fn, has_aux=True)(
            params, b, mcfg, jcfg)
        ref_loss, ref_grads = wm.batch_grad(
            params, batch["fields"], batch["target"], cfg)
    np.testing.assert_allclose(float(loss), float(ref_loss), rtol=1e-5)
    for (path, g), r in zip(jax.tree_util.tree_leaves_with_path(grads),
                            jax.tree.leaves(ref_grads)):
        scale = float(jnp.max(jnp.abs(r))) + 1e-12
        err = float(jnp.max(jnp.abs(g - r))) / scale
        assert err < 1e-4, (jax.tree_util.keystr(path), err)


def test_fp8_control_departs_from_f32(setup):
    cfg, _, _, params, batch = setup
    x = batch["fields"][0]
    with jax.default_matmul_precision("highest"):
        f32 = wm.forward(params, x, cfg, wm.gemm_f32)
        fp8 = wm.forward(params, x, cfg, wm.gemm_fp8)
    rel = float(jnp.linalg.norm(fp8 - f32) / jnp.linalg.norm(f32 - x))
    assert rel > 1e-2
