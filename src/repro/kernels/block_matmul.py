"""Pallas TPU blocked matmul with fused bias + GELU epilogue.

This is the compute hot-spot of WeatherMixer: the paper reduces the whole
model to dense matmuls (its Table 1 workloads are pure GEMM chains), so
the kernel-level contribution here is an MXU-shaped GEMM:

  y = epilogue(x @ w.T + b)      x: [M, K], w: [N, K], y: [M, N]

TPU adaptation (DESIGN.md): tiles are MXU-aligned (multiples of 128 on N
and K, of the dtype's sublane on M), the K-loop accumulates into a
float32 VMEM scratch (HBM -> VMEM -> MXU), and the epilogue (bias add +
GELU of the mixer MLP's first linear) is fused into the final K-step so
the activation never round-trips to HBM.  The grid is (M, N, K) with K innermost, so each step
fetches a new x and w tile: the tile's bm*bn/(bm+bn) sets the FLOP per
byte, which ``ops.tile_plan`` chooses per GEMM shape.

Validated in interpret mode on CPU against ref.py (the pure-jnp oracle);
on real TPU hardware the same pallas_call runs compiled.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


# Scoped VMEM one kernel may take (v5e has 128 MiB a core; the compiler's
# default is 16 MiB).  Tiles planned for 48 MiB ran fastest, or within
# 3.3 % of the fastest, of budgets from 16 to 96 MiB on every GEMM shape
# of wm-zoo-4t training and wm-1b serving on a v5e (PERF.md).
VMEM_LIMIT_BYTES = 48 * 2**20


def sublane(dtype) -> int:
    """Minimum second-to-last tile dim for ``dtype`` on the TPU (f32 8,
    bf16 16, int8/fp8 32) -- the single source of truth for both the
    block sizes ops picks (tile_plan, block_dims) and the legality assert
    below."""
    return {4: 8, 2: 16, 1: 32}.get(jnp.dtype(dtype).itemsize, 8)


def _kernel(x_ref, w_ref, b_ref, o_ref, acc_ref, *, n_k: int,
            epilogue: str):
    k = pl.program_id(2)

    @pl.when(k == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    acc_ref[...] += jax.lax.dot_general(
        x_ref[...], w_ref[...], (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32)

    @pl.when(k == n_k - 1)
    def _finish():
        out = acc_ref[...]
        if b_ref is not None:
            out = out + b_ref[...].astype(jnp.float32)      # (1, bn) row
        if epilogue == "gelu":
            out = jax.nn.gelu(out)
        elif epilogue == "silu":
            out = jax.nn.silu(out)
        o_ref[...] = out.astype(o_ref.dtype)


def block_matmul(x: jax.Array, w: jax.Array, b: Optional[jax.Array] = None,
                 *, block_m: int, block_n: int, block_k: int,
                 epilogue: str = "none",
                 interpret: bool = None) -> jax.Array:
    """y = epilogue(x @ w.T + b).  x: [M, K]; w: [N, K]; b: [N] or None.

    M, N, K must be multiples of the block sizes (ops.py pads and picks
    the sizes: ``ops.tile_plan``, whose ``ops.tile_vmem_bytes`` is this
    kernel's VMEM footprint).
    """
    m, k = x.shape
    n, k2 = w.shape
    assert k == k2, (x.shape, w.shape)
    assert x.dtype == w.dtype, (
        f"block_matmul needs one operand dtype (got {x.dtype} vs "
        f"{w.dtype}); cast at the linear-apply boundary (ops.py does)")
    assert m % block_m == 0 and n % block_n == 0 and k % block_k == 0, (
        f"shape ({m},{n},{k}) not divisible by blocks "
        f"({block_m},{block_n},{block_k})")
    # bf16 tiles need a 16-row sublane (f32: 8); ops floors the block
    # sizes accordingly, so by here block_m is already legal
    sl = sublane(x.dtype)
    assert block_m % sl == 0 or block_m == m, (
        f"block_m={block_m} below the {jnp.dtype(x.dtype).name} sublane "
        f"floor {sl}")
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    n_k = k // block_k
    grid = (m // block_m, n // block_n, n_k)

    in_specs = [
        pl.BlockSpec((block_m, block_k), lambda i, j, kk: (i, kk)),
        pl.BlockSpec((block_n, block_k), lambda i, j, kk: (j, kk)),
    ]
    args = [x, w]
    if b is not None:
        # the bias rides as a (1, N) row: Mosaic refuses a 1-D block whose
        # tiling differs from XLA's layout of the [N] operand
        in_specs.append(pl.BlockSpec((1, block_n), lambda i, j, kk: (0, j)))
        args.append(b.reshape(1, n))
        kernel = functools.partial(_kernel, n_k=n_k, epilogue=epilogue)
    else:
        kernel = functools.partial(
            lambda x_ref, w_ref, o_ref, acc_ref, **kw:
            _kernel(x_ref, w_ref, None, o_ref, acc_ref, **kw),
            n_k=n_k, epilogue=epilogue)

    return pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=in_specs,
        out_specs=pl.BlockSpec((block_m, block_n), lambda i, j, kk: (i, j)),
        out_shape=jax.ShapeDtypeStruct((m, n), x.dtype),
        scratch_shapes=[pltpu.VMEM((block_m, block_n), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            vmem_limit_bytes=VMEM_LIMIT_BYTES),
        interpret=interpret,
    )(*args)
