"""Interpret-mode parity suite for the fused-kernel hot path.

Single-process half: the Pallas compute engine (``kernel="pallas"``) must
match the XLA path within accumulation tolerance for forward AND
gradients (the custom VJP's backward GEMMs run the same Pallas kernel),
and ``ops.mixer_mlp`` must match the unfused two-matmul reference.

Distributed half (pseudo-mesh of 16 host-emulated devices, subprocess):
``ring_chunked`` == ``ring`` (bit-for-bit under bf16 and the Pallas
GEMM; under fp32 XLA GEMMs within the CPU dot's reduction-order
tolerance) and == ``rs`` within f32 reduction-order tolerance, with AD
through the chunked ring -- see
tests/dist_scenarios.py::scenario_ring_chunked_parity; and the 2-D
transposed Cannon on the Pallas step kernel == its dot_general lowering
(scenario_cannon_pallas_parity).
"""
import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.api import (JigsawConfig, linear_apply, linear_init,
                            mlp_apply, mlp_init)
from repro.kernels import ops, ref

KEY = jax.random.PRNGKey(0)
XLA = JigsawConfig(scheme="none", kernel="xla")
PALLAS = JigsawConfig(scheme="none", kernel="pallas")


def _tree_close(a, b, rtol, atol):
    flat_a, flat_b = jax.tree.leaves(a), jax.tree.leaves(b)
    return all(np.allclose(np.asarray(x), np.asarray(y), rtol=rtol,
                           atol=atol) for x, y in zip(flat_a, flat_b))


# ---------------------------------------------------------------------------
# block shrink (satellite: the dead ``bm`` fix)
# ---------------------------------------------------------------------------

def test_block_dims_shrink_small_gemm():
    """A 16-row GEMM must run a 16-row block, not pad to block_m=256."""
    bm, bn, bk = ops.block_dims(16, 300, 40, block_m=256, block_n=256,
                                block_k=512)
    assert bm == 16          # sublane-aligned ceiling of m, not block_m
    assert bn == 256         # round_up(300, 128)=384 > block_n: keep 256
    assert bk == 128         # lane ceiling of k=40


def test_block_dims_alignment_floors():
    bm, bn, bk = ops.block_dims(3, 5, 7, block_m=256, block_n=256,
                                block_k=512)
    assert (bm, bn, bk) == (8, 128, 128)
    bm16, _, _ = ops.block_dims(3, 5, 7, block_m=256, block_n=256,
                                block_k=512, dtype=jnp.bfloat16)
    assert bm16 == 16        # bf16 sublane floor


def test_matmul_small_rows_correct():
    """Post-fix regression: tiny-m GEMMs still numerically correct."""
    k1, k2, k3 = jax.random.split(KEY, 3)
    x = jax.random.normal(k1, (16, 40))
    w = jax.random.normal(k2, (300, 40)) * 0.05
    b = jax.random.normal(k3, (300,)) * 0.1
    y = ops.matmul(x, w, b, epilogue="gelu")
    r = ref.block_matmul_ref(x, w, b, "gelu")
    np.testing.assert_allclose(np.asarray(y), np.asarray(r), rtol=2e-5,
                               atol=2e-5)


# ---------------------------------------------------------------------------
# custom VJP: pallas grads == XLA grads
# ---------------------------------------------------------------------------

# (m, k, n): the ragged shapes run the forward, dx and dw GEMMs each on
# its own planned tile, one whole-dim block or several of no power of two
# with edge blocks and a masked K tail ("ktail": the forward's last K
# block holds 112 of 128 columns, the dw's 8).  atol: the f32 sums of the
# ragged cases run to ~1e3 terms of gradients up to ~1e3, so reduction
# order alone moves them by ~1e-3.
_GRAD_MKN = {"small": ((24, 72, 56), 2e-4),
             "wholedim": ((300, 700, 130), 2e-4),
             "ragged": ((1100, 2192, 700), 1e-3),
             "ktail": ((520, 6000, 200), 1e-3)}
_GRAD_CASES = (
    [pytest.param(bias, epi, "small", id=f"{bias}-{epi}")
     for bias in (True, False) for epi in ("none", "gelu", "silu")]
    + [pytest.param(True, epi, shape, id=f"True-{epi}-{shape}")
       for shape in ("wholedim", "ragged", "ktail")
       for epi in ("none", "gelu")])


@pytest.mark.parametrize("bias,epilogue,shape", _GRAD_CASES)
def test_matmul_grads_match_ref(bias, epilogue, shape):
    (m, k, n), atol = _GRAD_MKN[shape]
    k1, k2, k3 = jax.random.split(KEY, 3)
    x = jax.random.normal(k1, (m, k))
    w = jax.random.normal(k2, (n, k)) * 0.05
    b = jax.random.normal(k3, (n,)) * 0.1 if bias else None

    def f_pallas(*args):
        xx, ww, bb = (args if bias else (*args, None))
        return jnp.sum(ops.matmul(xx, ww, bb, epilogue=epilogue) ** 2)

    def f_ref(*args):
        xx, ww, bb = (args if bias else (*args, None))
        return jnp.sum(ref.block_matmul_ref(xx, ww, bb, epilogue) ** 2)

    args = (x, w, b) if bias else (x, w)
    nums = tuple(range(len(args)))
    gp = jax.grad(f_pallas, argnums=nums)(*args)
    gr = jax.grad(f_ref, argnums=nums)(*args)
    for a, c in zip(gp, gr):
        np.testing.assert_allclose(np.asarray(a), np.asarray(c),
                                   rtol=2e-4, atol=atol)


def test_linear_apply_pallas_vs_xla_fwd_and_grad():
    params = linear_init(KEY, 72, 56)
    x = jax.random.normal(jax.random.PRNGKey(1), (2, 24, 72))

    def loss(p, cfg):
        return jnp.sum(linear_apply(p, x, cfg) ** 2)

    vx, gx = jax.value_and_grad(loss)(params, XLA)
    vp, gp = jax.value_and_grad(loss)(params, PALLAS)
    np.testing.assert_allclose(float(vp), float(vx), rtol=1e-4)
    assert _tree_close(gp, gx, rtol=2e-3, atol=1e-3)


def test_linear_apply_pallas_fused_epilogue():
    """The epilogue knob fuses act(x@w.T+b) on the pallas path."""
    params = linear_init(KEY, 64, 48)
    x = jax.random.normal(jax.random.PRNGKey(2), (32, 64))
    y = linear_apply(params, x, PALLAS, epilogue="gelu")
    r = jax.nn.gelu(linear_apply(params, x, XLA))
    np.testing.assert_allclose(np.asarray(y), np.asarray(r), rtol=2e-5,
                               atol=2e-5)


# ---------------------------------------------------------------------------
# fused mixer MLP vs the unfused two-matmul reference
# ---------------------------------------------------------------------------

def test_mixer_mlp_fwd_and_grad_vs_unfused():
    params = mlp_init(KEY, 64, 128, 64)
    x = jax.random.normal(jax.random.PRNGKey(3), (2, 24, 64))

    def loss(p, cfg):
        return jnp.sum(mlp_apply(p, x, cfg) ** 2)

    vx, gx = jax.value_and_grad(loss)(params, XLA)
    vp, gp = jax.value_and_grad(loss)(params, PALLAS)
    np.testing.assert_allclose(float(vp), float(vx), rtol=1e-4)
    assert _tree_close(gp, gx, rtol=2e-3, atol=1e-3)


def test_mixer_mlp_no_bias():
    params = mlp_init(KEY, 64, 96, 32, bias=False)
    x = jax.random.normal(jax.random.PRNGKey(4), (16, 64))
    y = mlp_apply(params, x, PALLAS)
    r = mlp_apply(params, x, XLA)
    np.testing.assert_allclose(np.asarray(y), np.asarray(r), rtol=1e-4,
                               atol=1e-4)


def test_weathermixer_pallas_forward_matches_xla():
    """Full reduced WeatherMixer forward: fused kernels == XLA engine."""
    from repro.configs.registry import get_config
    from repro.models import registry as M

    cfg = get_config("weathermixer-1b").reduced()
    params = M.init(KEY, cfg)
    batch = {"fields": jax.random.normal(
        KEY, (2, cfg.wm_lat, cfg.wm_lon, cfg.wm_channels))}
    yx, _ = M.apply(params, batch, cfg, XLA)
    yp, _ = M.apply(params, batch, cfg, PALLAS)
    np.testing.assert_allclose(np.asarray(yp), np.asarray(yx), rtol=2e-4,
                               atol=2e-4)


# ---------------------------------------------------------------------------
# bf16 precision policy (ISSUE 5): pallas vs xla parity + resume roundtrip
# ---------------------------------------------------------------------------

BF16_XLA = JigsawConfig(scheme="none", kernel="xla",
                        compute_dtype=jnp.bfloat16)
BF16_PALLAS = JigsawConfig(scheme="none", kernel="pallas",
                           compute_dtype=jnp.bfloat16)


def test_matmul_bf16_fwd_matches_ref():
    """bf16 pallas GEMM (fp32 MXU accumulation, 16-row sublane tiles)
    matches the xla bf16 path within one-rounding tolerance."""
    k1, k2, k3 = jax.random.split(KEY, 3)
    x = jax.random.normal(k1, (24, 72)).astype(jnp.bfloat16)
    w = (jax.random.normal(k2, (56, 72)) * 0.05).astype(jnp.bfloat16)
    b = (jax.random.normal(k3, (56,)) * 0.1).astype(jnp.bfloat16)
    y = ops.matmul(x, w, b, epilogue="gelu")
    assert y.dtype == jnp.bfloat16
    r = ref.block_matmul_ref(x.astype(jnp.float32), w.astype(jnp.float32),
                             b.astype(jnp.float32), "gelu")
    np.testing.assert_allclose(np.asarray(y, dtype=np.float32),
                               np.asarray(r), rtol=2e-2, atol=2e-2)


def test_block_dims_bf16_sublane_tiling():
    """bf16 GEMMs tile 16-row sublanes (f32: 8) -- the MXU constraint."""
    bm, _, _ = ops.block_dims(20, 128, 128, block_m=256, block_n=256,
                              block_k=512, dtype=jnp.bfloat16)
    assert bm == 32          # round_up(20, 16), not round_up(20, 8)=24
    bm8, _, _ = ops.block_dims(20, 128, 128, block_m=256, block_n=256,
                               block_k=512, dtype=jnp.float32)
    assert bm8 == 24


def test_linear_apply_bf16_pallas_vs_xla_fwd_and_grad():
    """bf16 policy through linear_apply: pallas == xla for forward AND
    grads (the custom VJP casts grads back to the param dtype)."""
    params = linear_init(KEY, 72, 56)
    params = jax.tree.map(lambda p: p.astype(jnp.bfloat16), params)
    x = jax.random.normal(jax.random.PRNGKey(1), (2, 24, 72)
                          ).astype(jnp.bfloat16)

    def loss(p, cfg):
        return jnp.sum(linear_apply(p, x, cfg).astype(jnp.float32) ** 2)

    vx, gx = jax.value_and_grad(loss)(params, BF16_XLA)
    vp, gp = jax.value_and_grad(loss)(params, BF16_PALLAS)
    assert gp["w"].dtype == jnp.bfloat16     # grads back in param dtype
    np.testing.assert_allclose(float(vp), float(vx), rtol=2e-2)
    gx32 = jax.tree.map(lambda a: np.asarray(a, dtype=np.float32), gx)
    gp32 = jax.tree.map(lambda a: np.asarray(a, dtype=np.float32), gp)
    assert _tree_close(gp32, gx32, rtol=5e-2, atol=5e-1)


def test_mixer_mlp_bf16_fused_vs_unfused():
    params = mlp_init(KEY, 64, 128, 64)
    params = jax.tree.map(lambda p: p.astype(jnp.bfloat16), params)
    x = jax.random.normal(jax.random.PRNGKey(3), (2, 24, 64)
                          ).astype(jnp.bfloat16)
    yp = mlp_apply(params, x, BF16_PALLAS)
    yx = mlp_apply(params, x, BF16_XLA)
    assert yp.dtype == jnp.bfloat16
    np.testing.assert_allclose(np.asarray(yp, dtype=np.float32),
                               np.asarray(yx, dtype=np.float32),
                               rtol=5e-2, atol=5e-2)


def test_bf16_policy_resume_roundtrip(tmp_path):
    """A bf16-policy run checkpointed through the sharded writer resumes
    exactly: params restored bf16, Adam master weights restored fp32,
    and the continued loss history matches the uninterrupted run."""
    from repro.launch.engine import EngineConfig, TrainEngine

    path = str(tmp_path / "ck")

    def engine(**kw):
        return TrainEngine("weathermixer-1b", config=EngineConfig(
            steps=4, batch=2, log_every=1, precision="bf16", **kw))

    full = engine()
    h_full = full.run()

    interrupted = engine(ckpt=path, ckpt_every=2)
    interrupted.run()
    resumed = engine(resume=path + "-2")
    assert resumed.step_idx == 3
    assert resumed.params["encoder"]["w"].dtype == jnp.bfloat16
    assert resumed.opt_state["master"]["encoder"]["w"].dtype == jnp.float32
    assert resumed.opt_state["mu"]["encoder"]["w"].dtype == jnp.float32
    # the bf16 params must equal the fp32 masters cast down (the masters
    # are the source of truth the update writes through)
    np.testing.assert_array_equal(
        np.asarray(resumed.params["encoder"]["w"], dtype=np.float32),
        np.asarray(resumed.opt_state["master"]["encoder"]["w"]
                   .astype(jnp.bfloat16), dtype=np.float32))
    h_res = resumed.run()
    tail = [h for h in h_full if h["step"] >= 3]
    assert len(h_res) == len(tail)
    for a, b in zip(tail, h_res):
        assert a["loss"] == b["loss"] and a["grad_norm"] == b["grad_norm"]


def test_bf16_policy_resume_rejects_precision_mismatch(tmp_path):
    from repro.launch.engine import EngineConfig, TrainEngine

    path = str(tmp_path / "ck")
    eng = TrainEngine("weathermixer-1b", config=EngineConfig(
        steps=2, batch=2, log_every=1, precision="bf16", ckpt=path))
    eng.run()
    with pytest.raises(ValueError, match="precision"):
        TrainEngine("weathermixer-1b", config=EngineConfig(
            steps=2, batch=2, log_every=1, resume=path))


# ---------------------------------------------------------------------------
# distributed half: chunked-ring parity on a 16-device pseudo-mesh
# ---------------------------------------------------------------------------

def test_ring_chunked_parity_pseudo_mesh():
    env = dict(os.environ)
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=16"
    env["JAX_PLATFORMS"] = "cpu"
    script = os.path.join(os.path.dirname(__file__), "dist_scenarios.py")
    res = subprocess.run(
        [sys.executable, script, "ring_chunked_parity"], env=env,
        capture_output=True, text=True, timeout=600)
    assert res.returncode == 0 and "ALL-OK" in res.stdout, (
        f"\nstdout:\n{res.stdout[-3000:]}\nstderr:\n{res.stderr[-3000:]}")


# ---------------------------------------------------------------------------
# transposed Cannon on the Pallas step kernel; the 1-D ring's schedules
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("compute", ["fp32", "bf16"])
def test_cannon_pallas_parity_pseudo_mesh(compute):
    """``jigsaw_linear_2d_t(kernel="pallas")`` (the Cannon loop on the
    ``acc + w @ x`` step kernel) == ``kernel="xla"`` on a (1, 2, 2) mesh,
    forward and grads -- see dist_scenarios.scenario_cannon_pallas_parity.
    """
    env = dict(os.environ)
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=16"
    env["JAX_PLATFORMS"] = "cpu"
    script = os.path.join(os.path.dirname(__file__), "dist_scenarios.py")
    res = subprocess.run(
        [sys.executable, script, f"cannon_pallas_parity:{compute}"],
        env=env, capture_output=True, text=True, timeout=600)
    assert res.returncode == 0 and "ALL-OK" in res.stdout, (
        f"\nstdout:\n{res.stdout[-3000:]}\nstderr:\n{res.stderr[-3000:]}")


def test_jigsaw_config_validation():
    """Unknown knobs raise; silently-ignored combinations warn."""
    import warnings

    with pytest.raises(ValueError, match="scheme"):
        JigsawConfig(scheme="3d")
    with pytest.raises(ValueError, match="impl"):
        JigsawConfig(impl="ring_fuzed")
    with pytest.raises(ValueError, match="impl"):
        JigsawConfig(impl="ring_fused")
    with pytest.raises(ValueError, match="kernel"):
        JigsawConfig(kernel="triton")
    with pytest.warns(UserWarning, match="ignores"):
        JigsawConfig(scheme="2d", impl="ring_chunked")
    with warnings.catch_warnings():
        warnings.simplefilter("error")          # no spurious warnings
        JigsawConfig(scheme="1d", impl="ring_chunked", kernel="pallas")
        JigsawConfig(scheme="2d")               # default impl: fine


@pytest.mark.parametrize("kern", ["xla", "pallas"])
def test_ring_chunked_p1_smoke(kern):
    """A ring of one runs no hop: ``ring_matmul_chunked`` is the local
    GEMM, forward and grads equal to the dense GEMM on both engines."""
    from repro.core import jigsaw

    k1, k2 = jax.random.split(KEY)
    x = jax.random.normal(k1, (8, 24, 64))
    w = jax.random.normal(k2, (48, 64)) * 0.05

    def dense(xx, ww):
        return jnp.sum(jnp.einsum("btd,md->btm", xx, ww) ** 2)

    def chunked(xx, ww):
        y = jigsaw.ring_matmul_chunked(xx, ww, axis_name="model",
                                       axis_size=1, kernel=kern)
        return jnp.sum(y ** 2)

    v, g = jax.value_and_grad(chunked, argnums=(0, 1))(x, w)
    vr, gr = jax.value_and_grad(dense, argnums=(0, 1))(x, w)
    np.testing.assert_allclose(float(v), float(vr), rtol=1e-4)
    for a, b in zip(g, gr):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=1e-3, atol=1e-4)


def test_cannon_t_step_parity():
    """The fused multiply-accumulate step kernel (acc + w @ x, f32 VMEM
    accumulation) matches the reference einsum for forward AND grads
    (custom VJP: dw/dx ride the same blocked machinery)."""
    from repro.kernels import cannon

    k1, k2, k3 = jax.random.split(KEY, 3)
    w = jax.random.normal(k1, (20, 24)) * 0.1
    x = jax.random.normal(k2, (3, 24, 40))
    acc = jax.random.normal(k3, (3, 20, 40))

    def f_pallas(ww, xx, aa):
        return jnp.sum(cannon.cannon_t_step(ww, xx, aa) ** 2)

    def f_ref(ww, xx, aa):
        return jnp.sum((aa + jnp.einsum("mt,btc->bmc", ww, xx)) ** 2)

    y = cannon.cannon_t_step(w, x, acc)
    r = acc + jnp.einsum("mt,btc->bmc", w, x)
    np.testing.assert_allclose(np.asarray(y), np.asarray(r), rtol=2e-5,
                               atol=2e-5)
    gp = jax.grad(f_pallas, argnums=(0, 1, 2))(w, x, acc)
    gr = jax.grad(f_ref, argnums=(0, 1, 2))(w, x, acc)
    for a, b in zip(gp, gr):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=2e-4, atol=2e-4)
    # None starts a fresh accumulator
    y0 = cannon.cannon_t_step(w, x, None)
    np.testing.assert_allclose(np.asarray(y0),
                               np.asarray(jnp.einsum("mt,btc->bmc", w, x)),
                               rtol=2e-5, atol=2e-5)


def test_comm_schedule_rows():
    """``ring_chunked`` exposes one chunk GEMM per hop where ``ring``
    exposes none, at identical wire bytes; other impls have no row."""
    from repro.core import jigsaw

    ring = jigsaw.comm_schedule_jigsaw_1d(4096, 4096, 512, 8, impl="ring")
    chunked = jigsaw.comm_schedule_jigsaw_1d(4096, 4096, 512, 8,
                                             impl="ring_chunked")
    assert ring.flops_per_hop == 0.0
    assert chunked.flops_per_hop == 2.0 * 4096 * 512 * (4096 / 8) > 0
    assert chunked.bytes_per_hop == ring.bytes_per_hop
    assert chunked.bytes_per_device == ring.bytes_per_device
    assert chunked.hops == ring.hops == 7
    assert chunked.scheme == "jigsaw-1d-ring_chunked"
    for impl in ("ring_fused", "rs"):
        with pytest.raises(ValueError, match="impl"):
            jigsaw.comm_schedule_jigsaw_1d(4096, 4096, 512, 8, impl=impl)
