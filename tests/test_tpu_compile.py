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
import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import (Mesh, NamedSharding, PartitionSpec as P,
                          SingleDeviceSharding)

from repro.configs.registry import get_config
from repro.core import jigsaw
from repro.core.sharding import RULES_1D, RULES_2D
from repro.kernels import cannon, ops
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


def _compiles_as_tpu_kernel(fn, *args) -> str:
    text = jax.jit(fn).lower(*args).compile().as_text()
    assert "tpu_custom_call" in text
    return text


PATCH = WM.patch_dim(WM1B)                      # 8 * 8 * 69 = 4,416
ZOO_D, ZOO_TOK = 2192, 4320                     # wm-zoo-4t (Table 1 model 5)


@pytest.mark.parametrize("m,k,n", [
    # token-mixing GEMM: [C, T] @ tok_fc1.w[d_tok, T].T (T's last K block
    # masked in-kernel)
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
    # wm-1b decoder at bucket 1: one K block of the whole 4,320
    (TOKENS, WM1B.d_model, PATCH),
], ids=["token", "channel", "b4-encoder", "b4-token1", "b4-token2",
        "b4-channel", "b4-decoder", "zoo-token", "zoo-channel",
        "zoo-token-dx", "zoo-decoder-dw", "b1-decoder"])
def test_block_matmul_wm1b_gelu_epilogue(one_chip, m, k, n):
    """Each GEMM on its planned tile, with the gelu epilogue (the most VMEM
    a tile of that shape takes), on its operands as they are: no ``pad``
    in the compiled program."""
    def fn(x, w, b):
        return ops.matmul(x, w, b, epilogue="gelu", interpret=False)

    text = _compiles_as_tpu_kernel(fn, _sds((m, k), one_chip),
                                   _sds((n, k), one_chip),
                                   _sds((n,), one_chip))
    assert not re.search(r"\bpad\(", text)


def test_block_matmul_k_tail_past_strip(one_chip):
    """Caller-set blocks whose last K block ends 212 columns past K: the
    kernel selects in one 128-lane strip and zeroes the lanes past it."""
    def fn(x, w, b):
        return ops.matmul(x, w, b, epilogue="gelu", block_m=256,
                          block_n=256, block_k=256, interpret=False)

    _compiles_as_tpu_kernel(fn, _sds((512, 300), one_chip),
                            _sds((384, 300), one_chip),
                            _sds((384,), one_chip))


def test_matmul_dw_stacked_by_scan(one_chip):
    """The gradient of a scan over three stacked [2192, 2192] weights (the
    zoo's channel GEMMs): the scan's transpose stacks each dw with a
    dynamic-update-slice, which must not fuse into the kernel (XLA would
    compile that fusion under 16 MiB of scoped VMEM, below the tile's)."""
    def loss(ws, x):
        def body(h, w):
            return ops.matmul(h, w, None, interpret=False), None
        h, _ = jax.lax.scan(body, x, ws)
        return jnp.sum(h.astype(jnp.float32))

    _compiles_as_tpu_kernel(jax.grad(loss),
                            _sds((3, ZOO_D, ZOO_D), one_chip),
                            _sds((4096, ZOO_D), one_chip))


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
        return cannon._wx_raw(w, x, a, jnp.float32, interpret=False)

    _compiles_as_tpu_kernel(fn, _sds((m_l, t_l), one_chip),
                   _sds((1, t_l, c_l), one_chip),
                   _sds((1, m_l, c_l), one_chip, jnp.float32))


def _loss(y):
    return jnp.sum(y.astype(jnp.float32) ** 2)


@pytest.fixture
def kernels_compiled(monkeypatch):
    """The Jigsaw layers leave ``interpret`` to the kernels, which run
    interpreted unless the default backend is a TPU; the described chips
    are not the default backend here, so the trace is told it is one."""
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")


def test_cannon_2x2_token_mix(topo, kernels_compiled):
    """wm-1b's first token GEMM under 2-D Jigsaw on a (1, 2, 2) mesh of
    the described chips, ``kernel="pallas"``: the Cannon loop on the
    step kernel, forward and gradients, x [1, T, d_emb] and
    w [d_tok, T]."""
    d = topo.devices
    mesh = Mesh([[[d[0], d[1]], [d[2], d[3]]]], ("data", "mdom", "mtp"))

    def fn(x, w):
        return jax.value_and_grad(lambda xx, ww: _loss(
            jigsaw.jigsaw_linear_2d_t(xx, ww, rules=RULES_2D, mesh=mesh,
                                      kernel="pallas")),
            argnums=(0, 1))(x, w)

    text = _compiles_as_tpu_kernel(
        fn, _sds((1, TOKENS, WM1B.d_model),
                 NamedSharding(mesh, P(None, "mdom", "mtp"))),
        _sds((WM1B.wm_d_tok, TOKENS), NamedSharding(mesh, P("mdom", "mtp"))))
    assert "collective-permute" in text          # the skew and rotate hops


@pytest.mark.parametrize("direction", ["fwd", "bwd"])
def test_ring_chunked_4(topo, kernels_compiled, direction):
    """wm-1b's channel GEMM under 1-D Jigsaw on a 4-chip ring of the
    described chips, ``impl="ring_chunked"``, ``kernel="pallas"`` (the
    1-D path ``configs/weathermixer_1b.py`` selects): x [T, d_emb],
    w [d_ch, d_emb]."""
    mesh = Mesh([list(topo.devices)], ("data", "model"))
    xs = NamedSharding(mesh, P(None, "model"))

    def linear(x, w):
        return jigsaw.jigsaw_linear(x, w, rules=RULES_1D, mesh=mesh,
                                    impl="ring_chunked", kernel="pallas")

    fn = linear if direction == "fwd" else jax.value_and_grad(
        lambda x, w: _loss(linear(x, w)), argnums=(0, 1))
    text = _compiles_as_tpu_kernel(fn, _sds((TOKENS, WM1B.d_model), xs),
                                   _sds((WM1B.wm_d_ch, WM1B.d_model), xs))
    assert "collective-permute" in text          # the ring's hops
