"""jit'd public wrappers around the Pallas kernels (tile choice, reshapes,
custom VJPs).

``matmul`` is the MXU-tiled GEMM used as Jigsaw's compute engine
(``JigsawConfig(kernel="pallas")``): f32 VMEM accumulation, bias + GELU /
SiLU epilogue fused into the final K-step.  Its tile is read off each
GEMM's shape and dtype by ``tile_plan``, a small cost model of the
kernel, unless the caller passes ``block_m/n/k`` (then ``block_dims``
shrinks those toward the problem size, as it always has).  A custom VJP
makes the path trainable: the backward GEMMs (dx = dz @ w, dw = dz^T @ x)
are themselves routed through the same Pallas kernel, each with a plan
of its own shape, and fused epilogues recompute their pre-activation
with one extra kernel call (flash-attention-style recomputation) instead
of saving it.

``mixer_mlp`` is the drop-in fused path for the WeatherMixer mixing MLPs:
two MXU-tiled GEMMs with the GELU fused into the first's epilogue.  The
kernel reads the operands as they are: a block grid that does not divide
a dim ends in an edge block (and a masked last K block), so no operand
is padded and no result sliced.
"""
from __future__ import annotations

from functools import partial
from typing import Optional

import jax
import jax.numpy as jnp

from repro.kernels.block_matmul import (VMEM_LIMIT_BYTES, block_matmul,
                                        sublane as _sublane)
from repro.telemetry.spans import get_tracer

_ACTS = {"gelu": jax.nn.gelu, "silu": jax.nn.silu}

# ``tile_plan``'s model of one TPU v5e core.  The VMEM budget is the
# kernel's scoped VMEM less 1 MiB for the compiler's own scratch.
VMEM_BUDGET_BYTES = VMEM_LIMIT_BYTES - 2**20
_PEAK_FLOP_S = 197e12           # bf16 on the MXU
_HBM_BYTE_S = 655e9             # 80 % of the 819 GB/s peak: ridge 300 FLOP/B
_STEP_S = 0.35e-6               # pipeline overhead of one grid step
_PAD_SLACK = 0.01               # work a plan may pad beyond the ceilings
_LANE = 128


def _pad_to(a: jax.Array, dim: int, mult: int) -> jax.Array:
    rem = a.shape[dim] % mult
    if rem == 0:
        return a
    pad = [(0, 0)] * a.ndim
    pad[dim] = (0, mult - rem)
    return jnp.pad(a, pad)


def _round_up(n: int, mult: int) -> int:
    return ((n + mult - 1) // mult) * mult


def block_dims(m: int, n: int, k: int, *, block_m: int, block_n: int,
               block_k: int, dtype=jnp.float32):
    """Shrink the requested block sizes toward the problem size.

    m shrinks to its sublane-aligned ceiling, n and k to their lane (128)
    ceilings, so small GEMMs run a single right-sized block instead of
    padding up to the full default tile (a 16-row f32 GEMM runs a 16-row
    block, not a 256-row one).
    """
    bm = min(block_m, _round_up(m, _sublane(dtype)))
    bn = min(block_n, _round_up(n, 128))
    bk = min(block_k, _round_up(k, 128))
    return bm, bn, bk


def tile_vmem_bytes(bm: int, bn: int, bk: int, dtype) -> int:
    """VMEM a ``block_matmul`` tile holds, as the v5e compiler allocates
    it: two buffers each of the x, w, bias (a sublane-padded row) and out
    tiles, a third x tile, the f32 accumulator, and the two f32 [bm, bn]
    temporaries of a fused epilogue."""
    item = jnp.dtype(dtype).itemsize
    return ((3 * bm * bk + 2 * bn * bk + 2 * _sublane(dtype) * bn
             + 2 * bm * bn) * item + 3 * 4 * bm * bn)


def _block_sizes(dim: int, align: int) -> list:
    """Aligned blocks for a dim already rounded up to ``align``: for each
    grid count the least block that covers it, kept where the count times
    the block pads ``dim`` by at most ``_PAD_SLACK``."""
    sizes = set()
    for g in range(1, dim // align + 1):
        b = _round_up(-(-dim // g), align)
        if -(-dim // b) * b <= dim * (1 + _PAD_SLACK):
            sizes.add(b)
    return sorted(sizes)


def _tile_seconds(mp: int, np_: int, kp: int, bm: int, bn: int, bk: int,
                  item: int) -> float:
    """Modelled time of the block grid: each step the larger of its MXU
    time and its x and w tiles' HBM time, plus ``_STEP_S``."""
    steps = -(-mp // bm) * -(-np_ // bn) * -(-kp // bk)
    return steps * (max(2 * bm * bn * bk / _PEAK_FLOP_S,
                        (bm + bn) * bk * item / _HBM_BYTE_S) + _STEP_S)


def padded_dims(m: int, n: int, k: int, dtype):
    """The alignment ceilings: m to the dtype's sublane, n and k to the
    lane (128)."""
    return _round_up(m, _sublane(dtype)), _round_up(n, _LANE), \
        _round_up(k, _LANE)


def pad_share(m: int, n: int, k: int, bm: int, bn: int, bk: int,
              dtype) -> float:
    """Work the tile's block grid pads beyond the alignment ceilings, as a
    share of the work at the ceilings."""
    mp, np_, kp = padded_dims(m, n, k, dtype)
    return (_round_up(mp, bm) * _round_up(np_, bn) * _round_up(kp, bk)
            / (mp * np_ * kp) - 1.0)


def tile_plan(m: int, n: int, k: int, dtype=jnp.bfloat16):
    """The (bm, bn, bk) tile ``matmul`` runs an [m, k] x [n, k] GEMM with.

    Pure Python on the shape and dtype (no tracing, no device query).
    Blocks are legal for ``block_matmul`` (bm a multiple of the dtype's
    sublane, bn and bk of 128, or the whole padded dim), pad at most
    ``_PAD_SLACK`` of the work beyond the alignment ceilings, and fit
    ``VMEM_BUDGET_BYTES``; of those the plan takes the one
    ``_tile_seconds`` prices cheapest.  Large GEMMs thus get tiles of
    bm*bn/(bm+bn) FLOP per byte above the chip's ridge, and few steps.
    """
    item = jnp.dtype(dtype).itemsize
    mp, np_, kp = padded_dims(m, n, k, dtype)
    bms = _block_sizes(mp, _sublane(dtype))
    bns = _block_sizes(np_, _LANE)
    bks = _block_sizes(kp, _LANE)
    work_cap = mp * np_ * kp * (1 + _PAD_SLACK)
    best, best_s = None, float("inf")
    for bm in bms:
        if tile_vmem_bytes(bm, bns[0], bks[0], dtype) > VMEM_BUDGET_BYTES:
            break
        for bn in bns:
            if tile_vmem_bytes(bm, bn, bks[0], dtype) > VMEM_BUDGET_BYTES:
                break
            for bk in bks:
                if tile_vmem_bytes(bm, bn, bk, dtype) > VMEM_BUDGET_BYTES:
                    break
                if (_round_up(mp, bm) * _round_up(np_, bn)
                        * _round_up(kp, bk) > work_cap):
                    continue
                s = _tile_seconds(mp, np_, kp, bm, bn, bk, item)
                if s < best_s:
                    best, best_s = (bm, bn, bk), s
    return best


def gemm_tile(m: int, n: int, k: int, dtype, block_m: Optional[int] = None,
              block_n: Optional[int] = None,
              block_k: Optional[int] = None):
    """The blocks ``block_matmul`` runs the GEMM with: ``tile_plan``'s
    tile, or the caller's sizes shrunk by ``block_dims``, with a K block
    past K cut to the whole of K (one K step, nothing to mask).  An M or
    N block past its dim stays as it is: an edge block needs no mask and
    does the same MXU work, and a lane-wide N block keeps each column's
    sum in the same order on the CPU interpreter whether a GEMM runs
    whole or by column chunks (as the Jigsaw rings' parity needs).

    Recorded once per trace (that is, per compiled GEMM) as a
    ``gemm.plan`` event on the process tracer: the shape, the blocks, the
    plan's ``pad_share``, ``override``, and where the grid does not divide
    the shape, ``edge`` (``"m"``, ``"n"``, ``"mn"`` or ``""``) and
    ``k_masked``."""
    given = (block_m, block_n, block_k)
    override = given != (None, None, None)
    if override:
        if None in given:
            raise ValueError(f"matmul takes all of block_m/n/k or none, "
                             f"got {given}")
        tile = block_dims(m, n, k, block_m=block_m, block_n=block_n,
                          block_k=block_k, dtype=dtype)
    else:
        tile = tile_plan(m, n, k, dtype)
    share = pad_share(m, n, k, *tile, dtype)
    bm, bn, bk = tile[0], tile[1], min(tile[2], k)
    edge = "m" * (m % bm != 0) + "n" * (n % bn != 0)
    get_tracer().event("gemm.plan", m=m, n=n, k=k, bm=bm, bn=bn, bk=bk,
                       pad_share=share, override=override, edge=edge,
                       k_masked=k % bk != 0)
    return bm, bn, bk


def _matmul_raw(x, w, b, epilogue, block_m, block_n, block_k, interpret):
    """Run the kernel on the operands as they are (no pad, no slice).

    bf16 inputs run the MXU at its half-width rate with fp32 VMEM
    accumulation inside the kernel; the sublane floor is 16 rows for
    2-byte dtypes (the TPU tile constraint), so a bf16 GEMM never issues
    an 8-row tile the hardware cannot form.
    """
    if w.dtype != x.dtype:
        # policy casts happen at the linear-apply boundary; anything that
        # still arrives mixed (e.g. an fp32 cotangent against bf16
        # residuals) is unified to x's dtype -- the MXU needs one operand
        # width and the f32 scratch keeps the accumulation exact either way
        w = w.astype(x.dtype)
    m, k = x.shape
    n = w.shape[0]
    bm, bn, bk = gemm_tile(m, n, k, x.dtype, block_m, block_n, block_k)
    y = block_matmul(x, w, b, block_m=bm, block_n=bn, block_k=bk,
                     epilogue=epilogue, interpret=interpret)
    # XLA would fuse a consumer's dynamic-update-slice (a scan stacking
    # this result, as its transpose stacks each layer's dw) into the kernel
    # and compile that fusion under the default 16 MiB of scoped VMEM, not
    # the kernel's VMEM_LIMIT_BYTES: the barrier keeps the kernel whole.
    return jax.lax.optimization_barrier(y)


@partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6, 7))
def _matmul(x, w, b, epilogue, block_m, block_n, block_k, interpret):
    return _matmul_raw(x, w, b, epilogue, block_m, block_n, block_k,
                       interpret)


def _matmul_fwd(x, w, b, epilogue, block_m, block_n, block_k, interpret):
    y = _matmul_raw(x, w, b, epilogue, block_m, block_n, block_k, interpret)
    return y, (x, w, b)


def _matmul_bwd(epilogue, block_m, block_n, block_k, interpret, res, dy):
    x, w, b = res
    blk = (block_m, block_n, block_k, interpret)
    if epilogue == "none":
        dz = dy
    else:
        # Recompute the pre-activation z = x @ w.T + b with one more
        # kernel call (cheaper than saving the [M, N] f32 accumulator).
        z = _matmul_raw(x, w, b, "none", *blk).astype(jnp.float32)
        _, act_vjp = jax.vjp(_ACTS[epilogue], z)
        dz = act_vjp(dy.astype(jnp.float32))[0].astype(dy.dtype)
    # Backward GEMMs through the same MXU-tiled kernel, each planned from
    # its own shape:  dx[m, k] = dz @ w   and   dw[n, k] = dz^T @ x.
    dx = _matmul_raw(dz, w.T, None, "none", *blk).astype(x.dtype)
    dw = _matmul_raw(dz.T, x.T, None, "none", *blk).astype(w.dtype)
    db = jnp.sum(dz, axis=0).astype(b.dtype) if b is not None else None
    return dx, dw, db


_matmul.defvjp(_matmul_fwd, _matmul_bwd)


@partial(jax.jit, static_argnames=("epilogue", "block_m", "block_n",
                                   "block_k", "interpret"))
def matmul(x: jax.Array, w: jax.Array, b: Optional[jax.Array] = None, *,
           epilogue: str = "none", block_m: Optional[int] = None,
           block_n: Optional[int] = None, block_k: Optional[int] = None,
           interpret: bool = None) -> jax.Array:
    """Blocked y = epilogue(x @ w.T + b) for arbitrary 2-D shapes.

    The tile is ``tile_plan``'s for the shape unless all of ``block_m/n/k``
    are given.  Differentiable (custom VJP; backward GEMMs also run the
    Pallas kernel), so it can sit inside the distributed training hot
    path.
    """
    return _matmul(x, w, b, epilogue, block_m, block_n, block_k, interpret)


def matmul_nd(x: jax.Array, w: jax.Array, b: Optional[jax.Array] = None,
              **kw) -> jax.Array:
    """``matmul`` over the last dim of an arbitrary-rank x [..., d_in]."""
    lead = x.shape[:-1]
    y = matmul(x.reshape(-1, x.shape[-1]), w, b, **kw)
    return y.reshape(lead + (w.shape[0],))


@partial(jax.jit, static_argnames=("block_m", "block_n", "block_k",
                                   "interpret"))
def mixer_mlp(x: jax.Array, w1: jax.Array, b1: Optional[jax.Array],
              w2: jax.Array, b2: Optional[jax.Array], *,
              block_m: Optional[int] = None,
              block_n: Optional[int] = None,
              block_k: Optional[int] = None,
              interpret: bool = None) -> jax.Array:
    """Fused mixer MLP over the last dim: gelu(x @ w1.T + b1) @ w2.T + b2.

    x: [..., rows, d_in]; w1: [d_h, d_in]; w2: [d_out, d_h].
    """
    lead = x.shape[:-1]
    x2 = x.reshape(-1, x.shape[-1])
    h = matmul(x2, w1, b1, epilogue="gelu", block_m=block_m,
               block_n=block_n, block_k=block_k, interpret=interpret)
    y = matmul(h, w2, b2, epilogue="none", block_m=block_m,
               block_n=block_n, block_k=block_k, interpret=interpret)
    return y.reshape(lead + (w2.shape[0],))


@partial(jax.jit, static_argnames=("interpret",))
def ssd_intra(c, b, x, dt, dac, *, interpret=None):
    """Fused intra-chunk SSD (see kernels/ssd_chunk.py).  Accepts the
    mamba2 layout [B, nc, Q, H, ...] and flattens to the kernel grid."""
    from repro.kernels.ssd_chunk import ssd_intra_chunk
    return ssd_intra_chunk(c, b, x, dt, dac, interpret=interpret)
