"""Pallas kernel correctness: shape/dtype sweeps (hypothesis) against the
pure-jnp oracles in kernels/ref.py, executed in interpret mode on CPU."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings, strategies as st
from jax.experimental.pallas import tpu as pltpu

from repro.kernels import ops, ref
from repro.kernels.block_matmul import block_matmul
from repro.telemetry.spans import Tracer, set_tracer

KEY = jax.random.PRNGKey(0)


def _mk(m, k, n, dtype, seed=0):
    k1, k2, k3 = jax.random.split(jax.random.PRNGKey(seed), 3)
    x = jax.random.normal(k1, (m, k), dtype)
    w = (jax.random.normal(k2, (n, k), jnp.float32) * 0.05).astype(dtype)
    b = (jax.random.normal(k3, (n,), jnp.float32) * 0.1).astype(dtype)
    return x, w, b


@settings(max_examples=12, deadline=None)
@given(
    m=st.integers(1, 5), k=st.integers(1, 5), n=st.integers(1, 5),
    mul=st.sampled_from([64, 96, 128]),
    epilogue=st.sampled_from(["none", "gelu", "silu"]),
)
def test_matmul_shape_sweep(m, k, n, mul, epilogue):
    x, w, b = _mk(m * mul, k * mul, n * mul, jnp.float32)
    y = ops.matmul(x, w, b, epilogue=epilogue, block_m=128, block_n=128,
                   block_k=128)
    r = ref.block_matmul_ref(x, w, b, epilogue)
    np.testing.assert_allclose(np.asarray(y), np.asarray(r), rtol=2e-5,
                               atol=2e-5)


# (m, k, n) cases beyond the first two are ragged: the plan runs them as
# one whole-dim block, or as several blocks of no power of two
@pytest.mark.parametrize("dtype,tol,mkn,epilogue", [
    pytest.param(jnp.float32, 2e-5, (256, 384, 192), "gelu",
                 id="float32-2e-05"),
    pytest.param(jnp.bfloat16, 3e-2, (256, 384, 192), "gelu",
                 id="bfloat16-0.03"),
    pytest.param(jnp.float32, 2e-5, (300, 700, 130), "gelu",
                 id="float32-wholedim-gelu"),
    pytest.param(jnp.float32, 1e-4, (1100, 2192, 700), "none",
                 id="float32-ragged-none"),
    pytest.param(jnp.bfloat16, 3e-2, (1500, 600, 2192), "gelu",
                 id="bfloat16-ragged-gelu"),
    pytest.param(jnp.bfloat16, 3e-2, (300, 700, 130), "none",
                 id="bfloat16-wholedim-none"),
])
def test_matmul_dtypes(dtype, tol, mkn, epilogue):
    x, w, b = _mk(*mkn, dtype)
    y = ops.matmul(x, w, b, epilogue=epilogue)
    r = ref.block_matmul_ref(x, w, b, epilogue)
    np.testing.assert_allclose(np.asarray(y, np.float32),
                               np.asarray(r, np.float32), rtol=tol, atol=tol)


def _fwd_dx_dw(name, m, n, k):
    """A GEMM's (m, n, k) and those of its input and weight gradients."""
    return [(f"{name}", m, n, k), (f"{name}-dx", m, k, n),
            (f"{name}-dw", n, k, m)]


_T = 91 * 180                      # tokens of a 728x1440 grid, 8x8 patches
_PATCH = 8 * 8 * 69
# wm-zoo-4t (Table 1 model 5: d 2192, d_tok 4320, d_ch 2192) at batch 2:
# the forward GEMMs of encoder, token and channel MLPs and decoder, each
# with its dx and dw; then wm-1b (d 4320, d_tok 8640, d_ch 4320) forward
# GEMMs at serving buckets 1, 2 and 4; then a wm-1b channel GEMM whose
# output is a d/4 shard.
_PLAN_SHAPES = (
    _fwd_dx_dw("zoo-enc", 2 * _T, 2192, _PATCH)
    + _fwd_dx_dw("zoo-tok1", 2 * 2192, 4320, _T)
    + _fwd_dx_dw("zoo-tok2", 2 * 2192, _T, 4320)
    + _fwd_dx_dw("zoo-ch", 2 * _T, 2192, 2192)
    + _fwd_dx_dw("zoo-dec", 2 * _T, _PATCH, 2192)
    + [(f"wm1b-{g}-b{b}", b * m, n, k) for b in (1, 2, 4)
       for g, m, n, k in (("enc", _T, 4320, _PATCH),
                          ("tok1", 4320, 8640, _T),
                          ("tok2", 4320, _T, 8640),
                          ("ch", _T, 4320, 4320),
                          ("dec", _T, _PATCH, 4320))]
    + [("wm1b-ch-shard4", _T, 4320 // 4, 4320)])


@pytest.mark.parametrize("m,n,k", [s[1:] for s in _PLAN_SHAPES],
                         ids=[s[0] for s in _PLAN_SHAPES])
def test_tile_plan(m, n, k):
    """The plan's tile is legal, fits the VMEM budget, pads at most 1.5 %
    of the work beyond the alignment ceilings, is compute-bound with
    margin (>= 300 FLOP/B), and gives way to explicit sizes."""
    bf = jnp.bfloat16
    bm, bn, bk = ops.tile_plan(m, n, k, bf)
    mp, np_, kp = ops.padded_dims(m, n, k, bf)
    assert bm % 16 == 0 and 16 <= bm <= mp
    assert bn % 128 == 0 and 128 <= bn <= np_
    assert bk % 128 == 0 and 128 <= bk <= kp
    assert ops.tile_vmem_bytes(bm, bn, bk, bf) <= ops.VMEM_BUDGET_BYTES
    assert 0.0 <= ops.pad_share(m, n, k, bm, bn, bk, bf) <= 0.015
    assert bm * bn / (bm + bn) >= 300
    # a K block past K runs as the whole of K
    run = (bm, bn, min(bk, k))
    tracer = Tracer()
    prev = set_tracer(tracer)
    try:
        assert ops.gemm_tile(m, n, k, bf) == run
        assert ops.gemm_tile(m, n, k, bf, 128, 256, 512) == (128, 256, 512)
    finally:
        set_tracer(prev)
    plans = [e["args"] for e in tracer.chrome_events()
             if e["name"] == "gemm.plan"]
    assert [(p["bm"], p["bn"], p["bk"], p["override"]) for p in plans] == [
        (*run, False), (128, 256, 512, True)]
    assert plans[0]["m"] == m and plans[0]["n"] == n and plans[0]["k"] == k
    assert plans[0]["edge"] == "m" * (m % run[0] > 0) + "n" * (n % run[1] > 0)
    assert plans[0]["k_masked"] == (k % run[2] > 0)


def test_matmul_no_bias():
    x, w, _ = _mk(128, 128, 128, jnp.float32)
    y = ops.matmul(x, w, None)
    r = ref.block_matmul_ref(x, w, None)
    np.testing.assert_allclose(np.asarray(y), np.asarray(r), rtol=2e-5)


def test_matmul_unaligned_padding():
    """Ragged dims run unpadded: one whole-dim block each, no pad, no
    slice, and the result has the operands' shape."""
    x, w, b = _mk(300, 700, 130, jnp.float32, seed=3)
    y = ops.matmul(x, w, b)
    r = ref.block_matmul_ref(x, w, b)
    assert y.shape == (300, 130)
    np.testing.assert_allclose(np.asarray(y), np.asarray(r), rtol=2e-5,
                               atol=2e-5)


# The TPU interpreter with every out-of-bounds read NaN, as stale VMEM may
# be on the chip: an M or N edge block's stray rows must stay out of the
# result, and an unmasked K tail would make the whole of it NaN.  The K
# tails end in the last block's first 128 lanes ("k-tail-wide" with whole
# lanes of the block past them) or in a later strip ("mnk-edge").
_NAN_OOB = pltpu.InterpretParams(out_of_bounds_reads="uninitialized",
                                 uninitialized_memory="nan")
# (m, n, k), (bm, bn, bk)
_EDGE_SHAPES = {
    "m-edge": ((200, 128, 256), (64, 128, 128)),
    "n-edge": ((64, 300, 256), (64, 128, 128)),
    "k-tail": ((64, 128, 300), (64, 128, 128)),
    "k-tail-wide": ((64, 128, 300), (64, 128, 256)),
    "mnk-edge": ((200, 300, 400), (64, 128, 256)),
    "k-whole": ((72, 200, 330), (32, 128, 330)),
}


@pytest.mark.parametrize("bias", [True, False], ids=["bias", "nobias"])
@pytest.mark.parametrize("epilogue", ["none", "gelu", "silu"])
@pytest.mark.parametrize("dtype,tol", [(jnp.float32, 2e-5),
                                       (jnp.bfloat16, 3e-2)],
                         ids=["float32", "bfloat16"])
@pytest.mark.parametrize("shape", list(_EDGE_SHAPES))
def test_block_matmul_edge_blocks(shape, dtype, tol, epilogue, bias):
    (m, n, k), (bm, bn, bk) = _EDGE_SHAPES[shape]
    x, w, b = _mk(m, k, n, dtype, seed=5)
    b = b if bias else None
    y = block_matmul(x, w, b, block_m=bm, block_n=bn, block_k=bk,
                     epilogue=epilogue, interpret=_NAN_OOB)
    r = ref.block_matmul_ref(x, w, b, epilogue)
    assert y.shape == (m, n)
    np.testing.assert_allclose(np.asarray(y, np.float32),
                               np.asarray(r, np.float32), rtol=tol, atol=tol)


def _eqns(jaxpr):
    """Every equation of ``jaxpr`` and of the jaxprs nested in it."""
    for eqn in jaxpr.eqns:
        yield eqn
        for v in eqn.params.values():
            for sub in v if isinstance(v, (list, tuple)) else [v]:
                sub = getattr(sub, "jaxpr", sub)
                if hasattr(sub, "eqns"):
                    yield from _eqns(sub)


# wm-1b forward GEMMs at bucket 1 (m, n, k), the tile the plan gives them,
# and what of it overhangs the shape
_WM1B_B1 = {
    "enc": ((_T, 4320, _PATCH), (1024, 2176, 896), "mn", True),
    "tok2": ((4320, _T, 8640), (720, 1024, 4352), "n", True),
}


@pytest.mark.parametrize("gemm", list(_WM1B_B1))
def test_matmul_wm1b_runs_unpadded(gemm):
    """At wm-1b's bf16 shapes ``ops.matmul`` is one ``pallas_call`` over
    the operands as they are: a ceiling-division grid, no ``pad`` and no
    ``slice``, and a ``gemm.plan`` event that names the edges."""
    (m, n, k), tile, edge, k_masked = _WM1B_B1[gemm]
    sds = jax.ShapeDtypeStruct
    tracer = Tracer()
    prev = set_tracer(tracer)
    try:
        jaxpr = jax.make_jaxpr(ops.matmul)(
            sds((m, k), jnp.bfloat16), sds((n, k), jnp.bfloat16),
            sds((n,), jnp.bfloat16))
    finally:
        set_tracer(prev)
    prims = [e.primitive.name for e in _eqns(jaxpr.jaxpr)]
    assert "pad" not in prims and "slice" not in prims, prims
    calls = [e for e in _eqns(jaxpr.jaxpr)
             if e.primitive.name == "pallas_call"]
    assert len(calls) == 1
    assert calls[0].params["grid_mapping"].grid == tuple(
        -(-d // b) for d, b in zip((m, n, k), tile))
    plans = [e["args"] for e in tracer.chrome_events()
             if e["name"] == "gemm.plan"]
    assert [(p["bm"], p["bn"], p["bk"], p["edge"], p["k_masked"])
            for p in plans] == [(*tile, edge, k_masked)]


@settings(max_examples=8, deadline=None)
@given(rows=st.sampled_from([64, 128, 200]),
       d_in=st.sampled_from([128, 256]),
       d_h=st.sampled_from([128, 384]),
       d_out=st.sampled_from([64, 256]),
       lead=st.integers(1, 3))
def test_mixer_mlp_sweep(rows, d_in, d_h, d_out, lead):
    k1, k2, k3 = jax.random.split(KEY, 3)
    x = jax.random.normal(k1, (lead, rows, d_in))
    w1 = jax.random.normal(k2, (d_h, d_in)) * 0.05
    b1 = jnp.zeros((d_h,))
    w2 = jax.random.normal(k3, (d_out, d_h)) * 0.05
    b2 = jnp.ones((d_out,)) * 0.1
    y = ops.mixer_mlp(x, w1, b1, w2, b2)
    r = ref.mixer_mlp_ref(x, w1, b1, w2, b2)
    assert y.shape == (lead, rows, d_out)
    np.testing.assert_allclose(np.asarray(y), np.asarray(r), rtol=1e-4,
                               atol=1e-4)


def test_mixer_mlp_equals_model_mlp():
    """The fused kernel matches the model's (unfused) mixer MLP."""
    from repro.core.api import JigsawConfig, mlp_apply, mlp_init
    params = mlp_init(KEY, 128, 256, 128)
    x = jax.random.normal(KEY, (2, 64, 128))
    r = mlp_apply(params, x, JigsawConfig(scheme="none"))
    y = ops.mixer_mlp(x, params["fc1"]["w"], params["fc1"]["b"],
                      params["fc2"]["w"], params["fc2"]["b"])
    np.testing.assert_allclose(np.asarray(y), np.asarray(r), rtol=2e-4,
                               atol=2e-4)



def test_ssd_intra_kernel_matches_ref():
    from hypothesis import given, settings, strategies as st
    k = jax.random.split(KEY, 5)
    g, q, n, p = 6, 64, 32, 16
    c = jax.random.normal(k[0], (g, q, n)) * 0.3
    b = jax.random.normal(k[1], (g, q, n)) * 0.3
    x = jax.random.normal(k[2], (g, q, p))
    dt = jax.nn.softplus(jax.random.normal(k[3], (g, q)))
    da = -jnp.cumsum(dt * 0.1, axis=1)
    y = ops.ssd_intra(c, b, x, dt, da)
    r = ref.ssd_intra_ref(c, b, x, dt, da)
    np.testing.assert_allclose(np.asarray(y), np.asarray(r), rtol=2e-4,
                               atol=2e-4)


def test_ssd_intra_kernel_matches_model_scan():
    """Kernel == the intra-chunk part of the model's _ssd_chunked."""
    from repro.models.layers import _ssd_chunked
    bsz, s, h, p, n, chunk = 1, 64, 2, 8, 16, 64   # single chunk
    k = jax.random.split(KEY, 5)
    x = jax.random.normal(k[0], (bsz, s, h, p))
    dt = jax.nn.softplus(jax.random.normal(k[1], (bsz, s, h)))
    A = -jnp.exp(jax.random.normal(k[2], (h,)) * 0.3)
    B = jax.random.normal(k[3], (bsz, s, 1, n)) * 0.3
    C = jax.random.normal(k[4], (bsz, s, 1, n)) * 0.3
    y_full, _ = _ssd_chunked(x, dt, A, B, C, chunk)
    # kernel arrangement: G = bsz*h blocks of one chunk each
    Bh = jnp.repeat(B, h, axis=2)
    Ch = jnp.repeat(C, h, axis=2)
    dac = jnp.cumsum(dt * A[None, None, :], axis=1)
    tog = lambda t: jnp.moveaxis(t, 2, 1).reshape((bsz * h, s) + t.shape[3:])
    y_k = ops.ssd_intra(tog(Ch), tog(Bh), tog(x),
                        jnp.moveaxis(dt, 2, 1).reshape(bsz * h, s),
                        jnp.moveaxis(dac, 2, 1).reshape(bsz * h, s))
    y_k = jnp.moveaxis(y_k.reshape(bsz, h, s, p), 1, 2)
    np.testing.assert_allclose(np.asarray(y_k), np.asarray(y_full),
                               rtol=2e-3, atol=2e-3)
