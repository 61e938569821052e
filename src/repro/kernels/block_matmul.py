"""Pallas TPU blocked matmul with fused bias + GELU epilogue.

This is the compute hot-spot of WeatherMixer: the paper reduces the whole
model to dense matmuls (its Table 1 workloads are pure GEMM chains), so
the kernel-level contribution here is an MXU-shaped GEMM:

  y = epilogue(x @ w.T + b)      x: [M, K], w: [N, K], y: [M, N]

TPU adaptation (DESIGN.md): tiles are MXU-aligned (multiples of 128 on N
and K, of the dtype's sublane on M, or the whole dim), the K-loop
accumulates into a float32 VMEM scratch (HBM -> VMEM -> MXU), and the
epilogue (bias add + GELU of the mixer MLP's first linear) is fused into
the final K-step so the activation never round-trips to HBM.  The grid
is (M, N, K) with K innermost, so each step fetches a new x and w tile:
the tile's bm*bn/(bm+bn) sets the FLOP per byte, which ``ops.tile_plan``
chooses per GEMM shape.

Operands are read where they lie, unpadded: the grid is the ceiling of
each dim over its block.  An M or N edge block reads rows past the end
of x or w, but they only feed output rows and columns past [M, N], whose
writes Pallas drops.  A K tail cannot be dropped, so the last K step
zeroes the tail columns of both tiles in VMEM before the dot (a select,
not a multiply: out-of-bounds VMEM may hold NaN), selecting only in the
128-lane strip that holds the end of K; every step runs the one dot.

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


def _zero_past(ref, k_rem: int) -> None:
    """Zero a VMEM tile's columns from ``k_rem`` on, in place: a select on
    the 128-lane strip that holds column ``k_rem``, zeros past it."""
    lo = k_rem // 128 * 128
    strip = ref[:, lo:lo + 128]
    col = jax.lax.broadcasted_iota(jnp.int32, strip.shape, 1)
    ref[:, lo:lo + 128] = jnp.where(col < k_rem - lo, strip,
                                    jnp.zeros_like(strip))
    if lo + 128 < ref.shape[1]:
        ref[:, lo + 128:] = jnp.zeros((ref.shape[0], ref.shape[1] - lo - 128),
                                      ref.dtype)


def _kernel(x_ref, w_ref, b_ref, o_ref, acc_ref, *, n_k: int, k_rem: int,
            widen: bool, epilogue: str):
    k = pl.program_id(2)

    @pl.when(k == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    if k_rem:
        # the last K block overhangs K: its columns past k_rem hold stale
        # VMEM in both tiles, which must not reach the sums
        @pl.when(k == n_k - 1)
        def _tail():
            _zero_past(x_ref, k_rem)
            _zero_past(w_ref, k_rem)

    x, w = x_ref[...], w_ref[...]
    if widen:
        x, w = x.astype(jnp.float32), w.astype(jnp.float32)
    acc_ref[...] += jax.lax.dot_general(
        x, w, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32)

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

    Any M, N, K: the blocks need not divide them (edge blocks, and a
    masked last K block; see the module docstring).  Each block is a
    multiple of its alignment (the dtype's sublane for ``block_m``, 128
    for ``block_n`` and ``block_k``) or its whole dim.  ``ops.tile_plan``
    picks the sizes; ``ops.tile_vmem_bytes`` is this kernel's VMEM
    footprint.
    """
    m, k = x.shape
    n, k2 = w.shape
    assert k == k2, (x.shape, w.shape)
    assert x.dtype == w.dtype, (
        f"block_matmul needs one operand dtype (got {x.dtype} vs "
        f"{w.dtype}); cast at the linear-apply boundary (ops.py does)")
    sl = sublane(x.dtype)
    for name, blk, dim, align in (("block_m", block_m, m, sl),
                                  ("block_n", block_n, n, 128),
                                  ("block_k", block_k, k, 128)):
        assert blk % align == 0 or blk == dim, (
            f"{name}={blk} is neither a multiple of {align} "
            f"({jnp.dtype(x.dtype).name}) nor the whole dim {dim}")
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    n_k = pl.cdiv(k, block_k)
    grid = (pl.cdiv(m, block_m), pl.cdiv(n, block_n), n_k)
    # Interpreted on the CPU, whose dot has no bf16 x bf16 -> f32 form for
    # every operand layout XLA folds into it; a product of two bf16 values
    # is exact in f32, so widening them first gives the same sums.
    static = dict(n_k=n_k, k_rem=k % block_k, epilogue=epilogue,
                  widen=bool(interpret) and x.dtype != jnp.float32)

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
        kernel = functools.partial(_kernel, **static)
    else:
        kernel = functools.partial(
            lambda x_ref, w_ref, o_ref, acc_ref, **kw:
            _kernel(x_ref, w_ref, None, o_ref, acc_ref, **kw),
            **static)

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
