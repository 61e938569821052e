"""The transposed-Cannon step kernel of the 2-D Jigsaw token mix.

``jigsaw_matmul_2d_t`` (core/jigsaw.py) under ``kernel="pallas"`` runs
its q Cannon steps here: ``cannon_t_loop`` multiplies the already-skewed
local blocks with ``cannon_t_step`` and rotates them between steps with
``ppermute`` (w along the tp axis, x along the dom axis).

``cannon_t_step`` is one ``pallas_call``: ``acc + w @ x`` contracting
x's second-to-last dim, a K-blocked MXU GEMM with f32 VMEM accumulation
whose epilogue adds the previous steps' accumulator (``_wx_kernel``).
Its custom VJP runs the backward GEMMs on the same blocked machinery:
dw on ``ops.matmul``, dx on ``_wx_raw`` with w transposed.  The loop
itself is differentiated by AD (``ppermute`` transposes to the reverse
rotation).
"""
from __future__ import annotations

import functools
import math
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels import ops
from repro.kernels.block_matmul import sublane


def _wx_kernel(w_ref, x_ref, a_ref, o_ref, acc_ref, *, n_k: int):
    """One (L, m, c) output block of ``out = a + w @ x``: K-blocked MXU
    GEMM with f32 VMEM accumulation, cross-step accumulator add fused into
    the epilogue (the Cannon multiply-accumulate in one kernel)."""
    kk = pl.program_id(3)

    @pl.when(kk == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    acc_ref[...] += jax.lax.dot_general(
        w_ref[...], x_ref[0], (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)

    @pl.when(kk == n_k - 1)
    def _epilogue():
        o_ref[0] = (a_ref[0].astype(jnp.float32)
                    + acc_ref[...]).astype(o_ref.dtype)


def _wx_raw(w: jax.Array, x: jax.Array, a: jax.Array, out_dtype,
            block_m: int = 256, block_c: int = 256, block_k: int = 512,
            interpret: Optional[bool] = None) -> jax.Array:
    """w [m, t] @ x [L, t, c] + a [L, m, c] -> [L, m, c] (out_dtype)."""
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    ll, t, c = x.shape
    m = w.shape[0]
    # m: sublane of w/out; t: lane of w AND sublane of x (128 covers both);
    # c: lane of x/out.
    bm = min(block_m, ops._round_up(m, sublane(w.dtype)))
    bk = min(block_k, ops._round_up(t, 128))
    bc = min(block_c, ops._round_up(c, 128))
    wp = ops._pad_to(ops._pad_to(w, 0, bm), 1, bk)
    xp = ops._pad_to(ops._pad_to(x, 1, bk), 2, bc)
    ap = ops._pad_to(ops._pad_to(a, 1, bm), 2, bc)
    mp, tp_, cp = wp.shape[0], wp.shape[1], xp.shape[2]
    n_k = tp_ // bk
    grid = (ll, mp // bm, cp // bc, n_k)
    out = pl.pallas_call(
        functools.partial(_wx_kernel, n_k=n_k),
        grid=grid,
        in_specs=[
            pl.BlockSpec((bm, bk), lambda b, i, j, kk: (i, kk)),
            pl.BlockSpec((1, bk, bc), lambda b, i, j, kk: (b, kk, j)),
            pl.BlockSpec((1, bm, bc), lambda b, i, j, kk: (b, i, j)),
        ],
        out_specs=pl.BlockSpec((1, bm, bc), lambda b, i, j, kk: (b, i, j)),
        out_shape=jax.ShapeDtypeStruct((ll, mp, cp), out_dtype),
        scratch_shapes=[pltpu.VMEM((bm, bc), jnp.float32)],
        interpret=interpret,
    )(wp, xp, ap)
    return out[:, :m, :c]


@functools.partial(jax.custom_vjp, nondiff_argnums=(3,))
def _wx_acc(w, x, a, out_name):
    return _wx_raw(w, x, a, jnp.dtype(out_name))


def _wx_acc_fwd(w, x, a, out_name):
    return _wx_acc(w, x, a, out_name), (w, x)


def _wx_acc_bwd(out_name, res, dy):
    w, x = res
    # d(a + w @ x): da = dy (identity in the accum dtype); the two GEMMs
    # run the same blocked Pallas machinery (ops-style transposed args).
    da = dy
    ll, t, c = x.shape
    m = w.shape[0]
    # dw[m, t] = sum_l dy_l @ x_l^T: flatten (L, c) into one contraction.
    dyt = jnp.moveaxis(dy, 1, 0).reshape(m, ll * c)
    xt = jnp.moveaxis(x, 1, 0).reshape(t, ll * c)
    dw = ops.matmul(dyt.astype(x.dtype), xt, None,
                    epilogue="none").astype(w.dtype)
    # dx[l, t, c] = w^T @ dy_l: the same wx kernel with w transposed
    # (transpose-in-backward, as in ops._matmul_bwd).
    zeros = jnp.zeros((ll, t, c), dy.dtype)
    dx = _wx_raw(w.T.astype(dy.dtype), dy, zeros,
                 jnp.dtype(out_name)).astype(x.dtype)
    return dw, dx, da


_wx_acc.defvjp(_wx_acc_fwd, _wx_acc_bwd)


def cannon_t_step(w: jax.Array, x: jax.Array, acc: Optional[jax.Array],
                  *, accum_dtype=jnp.float32) -> jax.Array:
    """One transposed-Cannon multiply-accumulate step on the MXU:
    ``acc + w @ x`` contracting x's second-to-last dim.

    w: [m_l, t_l]; x: [..., t_l, c_l]; acc: [..., m_l, c_l] in
    ``accum_dtype`` (None starts a fresh accumulator).  The cross-step add
    is fused into the GEMM epilogue so each Cannon step is ONE pallas_call;
    differentiable via a custom VJP whose backward GEMMs run the same
    blocked kernel.
    """
    out_dt = jnp.dtype(accum_dtype or x.dtype)
    lead = x.shape[:-2]
    ll = math.prod(lead) if lead else 1
    t, c = x.shape[-2], x.shape[-1]
    m = w.shape[0]
    x3 = x.reshape(ll, t, c)
    if acc is None:
        a3 = jnp.zeros((ll, m, c), out_dt)
    else:
        a3 = acc.reshape(ll, m, c).astype(out_dt)
    y = _wx_acc(w, x3, a3, out_dt.name)
    return y.reshape(lead + (m, c))


def cannon_t_loop(wl: jax.Array, xl: jax.Array, *, dom_axis: str,
                  tp_axis: str, q: int, accum_dtype) -> jax.Array:
    """The q-step transposed-Cannon loop on the step kernel: one fused
    multiply-accumulate pallas_call per step, rotate hops via ppermute.
    Operands must already be skewed.  Differentiable (cannon_t_step's
    custom VJP + ppermute's native transpose)."""
    acc = cannon_t_step(wl, xl, None, accum_dtype=accum_dtype)
    perm = [(t, (t - 1) % q) for t in range(q)]
    for _ in range(q - 1):
        wl = jax.lax.ppermute(wl, tp_axis, perm)
        xl = jax.lax.ppermute(xl, dom_axis, perm)
        acc = cannon_t_step(wl, xl, acc, accum_dtype=accum_dtype)
    return acc
