"""Compile the Pallas kernels of the main path for a TPU v5e, at the
paper's widths, without a chip attached.

Interpret-mode parity (test_kernels.py, test_kernel_parity.py) cannot
catch a block shape the TPU compiler refuses or a kernel that overruns
fast memory.  Here each kernel is lowered against a described
``v5e:2x2`` topology and compiled by the TPU compiler; the compiled HLO
must hold the kernel as a ``tpu_custom_call`` (compiled, not
interpreted).  Nothing runs, so these tests say nothing about results or
times.

The topology is described inside a module-scoped fixture only: the TPU
library may be loaded by one process at a time, and every test worker
imports this file.
"""
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import (Mesh, NamedSharding, PartitionSpec as P,
                          SingleDeviceSharding)

from repro.configs.registry import get_config
from repro.kernels import fused_ring, ops
from repro.models import weathermixer as WM

WM1B = get_config("weathermixer-1b")
TOKENS = WM.n_tokens(WM1B)                      # 91 * 180 = 16,380
BF16 = jnp.bfloat16


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:          # no TPU compiler in this installation
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _sds(shape, sharding, dtype=BF16):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _compiles_as_tpu_kernel(fn, *args) -> None:
    text = jax.jit(fn).lower(*args).compile().as_text()
    assert "tpu_custom_call" in text


PATCH = WM.patch_dim(WM1B)                      # 8 * 8 * 69 = 4,416
ZOO_D, ZOO_TOK = 2192, 4320                     # wm-zoo-4t (Table 1 model 5)


@pytest.mark.parametrize("m,k,n", [
    # token-mixing GEMM: [C, T] @ tok_fc1.w[d_tok, T].T (T padded in-kernel)
    (WM1B.d_model, TOKENS, WM1B.wm_d_tok),
    # channel-mixing GEMM: [T, d] @ ch_fc1.w[d_ch, d].T
    (TOKENS, WM1B.d_model, WM1B.wm_d_ch),
    # wm-1b at serving bucket 4: encoder, both token GEMMs, channel, decoder
    (4 * TOKENS, PATCH, WM1B.d_model),
    (4 * WM1B.d_model, TOKENS, WM1B.wm_d_tok),
    (4 * WM1B.d_model, WM1B.wm_d_tok, TOKENS),
    (4 * TOKENS, WM1B.d_model, WM1B.wm_d_ch),
    (4 * TOKENS, WM1B.d_model, PATCH),
    # wm-zoo-4t at batch 2: token and channel GEMMs, the token GEMM's dx
    # and the decoder's dw
    (2 * ZOO_D, TOKENS, ZOO_TOK),
    (2 * TOKENS, ZOO_D, ZOO_D),
    (2 * ZOO_D, ZOO_TOK, TOKENS),
    (PATCH, 2 * TOKENS, ZOO_D),
], ids=["token", "channel", "b4-encoder", "b4-token1", "b4-token2",
        "b4-channel", "b4-decoder", "zoo-token", "zoo-channel",
        "zoo-token-dx", "zoo-decoder-dw"])
def test_block_matmul_wm1b_gelu_epilogue(one_chip, m, k, n):
    """Each GEMM on its planned tile, with the gelu epilogue (the most VMEM
    a tile of that shape takes)."""
    def fn(x, w, b):
        return ops.matmul(x, w, b, epilogue="gelu", interpret=False)

    _compiles_as_tpu_kernel(fn, _sds((m, k), one_chip), _sds((n, k), one_chip),
                   _sds((n,), one_chip))


def test_mixer_mlp_wm1b_channel(one_chip):
    d, h = WM1B.d_model, WM1B.wm_d_ch

    def fn(x, w1, b1, w2, b2):
        return ops.mixer_mlp(x, w1, b1, w2, b2, interpret=False)

    _compiles_as_tpu_kernel(fn, _sds((1, TOKENS, d), one_chip),
                   _sds((h, d), one_chip), _sds((h,), one_chip),
                   _sds((d, h), one_chip), _sds((d,), one_chip))


def test_cannon_t_step_kernel(one_chip):
    """The transposed-Cannon multiply-accumulate kernel (``acc + w @ x``)
    at a 2x2 grid's local token-mix block of wm-1b: w [d_tok/2, T/2],
    x [1, T/2, d/2]."""
    m_l, t_l, c_l = WM1B.wm_d_tok // 2, TOKENS // 2, WM1B.d_model // 2

    def fn(w, x, a):
        return fused_ring._wx_raw(w, x, a, jnp.float32, interpret=False)

    _compiles_as_tpu_kernel(fn, _sds((m_l, t_l), one_chip),
                   _sds((1, t_l, c_l), one_chip),
                   _sds((1, m_l, c_l), one_chip, jnp.float32))


def test_fused_cannon_2x2(topo):
    """The fused q-hop Cannon kernel (rotates as in-kernel remote copies)
    on a 2x2 mesh of the described chips, at blocks under its VMEM
    budget."""
    d = topo.devices
    mesh = Mesh([[d[0], d[1]], [d[2], d[3]]], ("mdom", "mtp"))
    ll, m_l, t_l, c_l = 1, 256, 512, 512
    assert fused_ring.cannon_footprint_bytes(
        ll, m_l, t_l, c_l, BF16) <= fused_ring.VMEM_BUDGET_BYTES

    def local(w, x):
        return fused_ring._cannon_fwd_tpu(
            w, x[0], dom_axis="mdom", tp_axis="mtp", q=2,
            accum_dtype=jnp.float32, mesh_axes=("mdom", "mtp"))[None]

    fn = jax.shard_map(local, mesh=mesh,
                       in_specs=(P("mdom", "mtp"),
                                 P(None, None, "mdom", "mtp")),
                       out_specs=P(None, None, "mdom", "mtp"),
                       check_vma=False)
    _compiles_as_tpu_kernel(
        fn, _sds((2 * m_l, 2 * t_l), NamedSharding(mesh, P("mdom", "mtp"))),
        _sds((1, ll, 2 * t_l, 2 * c_l),
             NamedSharding(mesh, P(None, None, "mdom", "mtp"))))


@pytest.mark.parametrize("direction", ["fwd", "bwd"])
def test_fused_ring_4(topo, direction):
    """The one-kernel 1-D ring (in-kernel RDMA hops) on a 4-chip ring, at
    a block under the fused path's VMEM guard."""
    mesh = Mesh(list(topo.devices), ("model",))
    p, rows, d_local, m = 4, 256, 1024, 4096
    assert fused_ring.fits_vmem(rows, d_local, m, p, BF16, jnp.float32)
    xs = NamedSharding(mesh, P(None, "model"))

    if direction == "fwd":
        def local(x, w):
            return fused_ring._ring_fwd_tpu(x, w, "model", p, jnp.float32,
                                            ("model",))
        args = (_sds((rows, p * d_local), xs), _sds((m, p * d_local), xs))
        out_specs = P(None, "model")
    else:
        def local(x, w, dy):
            return fused_ring._ring_bwd_tpu(x, w, dy, "model", p,
                                            ("model",))
        args = (_sds((rows, p * d_local), xs), _sds((m, p * d_local), xs),
                _sds((rows, m), xs))
        out_specs = (P(None, "model"), P(None, "model"))
    fn = jax.shard_map(local, mesh=mesh,
                       in_specs=tuple(P(None, "model") for _ in args),
                       out_specs=out_specs, check_vma=False)
    _compiles_as_tpu_kernel(fn, *args)
