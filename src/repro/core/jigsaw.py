"""Jigsaw distributed matrix-matrix multiplication (the paper's core).

The paper defines Jigsaw as a zero-memory-redundancy distributed matmul in
which BOTH the activations X and the weights W are block-sharded, and the
contraction ``X @ W.T`` is completed by exchanging partial sums between
ranks while each rank computes its local block (communication overlapped
with computation, MPI point-to-point in the paper).

TPU/JAX adaptation (see DESIGN.md §2):

* **1-D Jigsaw** (paper §4.1, "2-way", generalized here to n-way): X is
  sharded along its last (channel) dim, W along its contracting dim.  Each
  rank computes the full partial product ``X_r @ W_r.T`` and the partial
  sums are combined with a *ring reduce-scatter*, leaving the output
  sharded along its last dim -- the same layout as the input, so layers
  compose without any re-sharding and no weight is ever allgathered.

  Five interchangeable implementations:
    - ``ring``  : explicit ppermute ring of partial-sum chunks.  The whole
                  local partial product is computed up-front with ONE GEMM
                  and the ring then only moves chunks of it -- an
                  approximation of the paper's schedule with zero
                  guaranteed overlap (the compute is finished before the
                  first hop is issued).
    - ``ring_chunked`` : the paper's actual algorithm.  The local weight
                  block is split into p output-chunks and chunk j's GEMM
                  is issued immediately before hop j's ppermute, so every
                  hop's send can overlap the NEXT chunk's compute ("each
                  hop's send overlaps the next chunk's compute", §4).
                  GEMMs and hops are still separate HLOs: overlap is
                  XLA-best-effort.
    - ``rs``    : ``jax.lax.psum_scatter`` -- XLA's native reduce-scatter,
                  which lowers to the same ring on the ICI torus but lets
                  the compiler schedule the overlap.
    - ``gspmd`` : no explicit collectives; sharding constraints only.  XLA
                  GSPMD derives the schedule.  (beyond-paper comparison)
    - ``allreduce`` : Megatron-style completion, a full ``psum`` and then
                  a slice of the local chunk: twice the bytes of the
                  reduce-scatter.  (comparison baseline)

  The local GEMMs route through either XLA's dot_general or the MXU-tiled
  Pallas kernel (``kernel="pallas"``, kernels/block_matmul.py): f32 VMEM
  accumulation, differentiable via a custom VJP whose backward GEMMs run
  the same kernel.

* **2-D Jigsaw** (paper §4.2, "4-way", generalized here to p x q): X is
  sharded over (token/longitude x channel) and W over (out x in) blocks;
  the contraction is Cannon's algorithm (the paper cites Cannon/SUMMA as
  the underlying idea) via ppermute skew + rotate steps.

Both are differentiable through JAX AD: the transpose of a ring
reduce-scatter is a ring allgather, which reproduces the paper's
"backward pass is the transposed multiplication, performed analogously".
"""
from __future__ import annotations

import dataclasses
from functools import partial
from typing import Optional

import jax
import jax.numpy as jnp
from jax import shard_map
from jax.sharding import PartitionSpec as P, get_abstract_mesh

from repro.core.sharding import ShardingRules, constrain

Impl1D = ("ring", "ring_chunked", "rs", "gspmd", "allreduce")
Kernels = ("xla", "pallas")


# --------------------------------------------------------------------------
# Ring collectives (paper-faithful explicit schedules)
# --------------------------------------------------------------------------

def ring_reduce_scatter(x: jax.Array, axis_name: str, axis_size: int,
                        scatter_dim: int = -1,
                        accum_dtype: Optional[jnp.dtype] = None) -> jax.Array:
    """Ring reduce-scatter of ``x`` along ``axis_name``.

    Every rank holds a full partial sum ``x``; afterwards rank ``r`` holds
    chunk ``r`` of ``sum_over_ranks(x)`` along ``scatter_dim``.  This is the
    n-way generalization of the paper's 2-way partial-sum exchange: at each
    of the p-1 steps a rank forwards its accumulator to the next neighbour
    while (in the lowered schedule) computing/adding the next local chunk.

    Mixed precision (core/precision): the WIRE format is ``x.dtype`` --
    every ``ppermute`` hop ships x.dtype bytes (bf16 halves per-hop ICI
    volume vs fp32) -- while the adds between hops run in ``accum_dtype``
    (rounding once per hop at the cast-down for the wire instead of
    accumulating error in bf16).  ``accum_dtype=None`` or == x.dtype is
    bit-identical to the unparameterized schedule.
    """
    p = axis_size
    if p == 1:
        return x
    dim = scatter_dim % x.ndim
    if x.shape[dim] % p != 0:
        raise ValueError(
            f"ring_reduce_scatter: dim {dim} of {x.shape} not divisible by {p}")
    chunk = x.shape[dim] // p
    idx = jax.lax.axis_index(axis_name)
    acc_dt = accum_dtype or x.dtype

    def get(j):
        c = jax.lax.dynamic_slice_in_dim(x, j * chunk, chunk, axis=dim)
        return c.astype(acc_dt)

    perm = [(i, (i + 1) % p) for i in range(p)]
    # Initialize with the chunk destined for our successor ring-walk; after
    # p-1 shift+add steps the accumulator is exactly chunk ``idx`` of the
    # global sum (see tests/test_jigsaw.py for the algebra check).
    acc = get((idx + p - 1) % p)
    for s in range(p - 1):
        acc = jax.lax.ppermute(acc.astype(x.dtype), axis_name, perm)
        acc = acc.astype(acc_dt) + get((idx - 2 - s) % p)
    return acc.astype(x.dtype)


def ring_all_gather(x: jax.Array, axis_name: str, axis_size: int,
                    gather_dim: int = -1) -> jax.Array:
    """Ring allgather (transpose of ring_reduce_scatter); used for
    comparison baselines, not by Jigsaw itself (zero redundancy!)."""
    p = axis_size
    if p == 1:
        return x
    dim = gather_dim % x.ndim
    idx = jax.lax.axis_index(axis_name)
    perm = [(i, (i + 1) % p) for i in range(p)]
    pieces = [x]
    cur = x
    for _ in range(p - 1):
        cur = jax.lax.ppermute(cur, axis_name, perm)
        pieces.append(cur)
    # piece j in ``pieces`` originated at rank (idx - j) % p; reorder into
    # rank order before concatenating along ``dim``.
    stacked = jnp.stack(pieces, axis=0)           # [p, ..., chunk]
    order = (idx - jnp.arange(p, dtype=jnp.int32)) % p
    inv = jnp.zeros((p,), jnp.int32).at[order].set(
        jnp.arange(p, dtype=jnp.int32))
    stacked = jnp.take(stacked, inv, axis=0)
    return jnp.concatenate([stacked[j] for j in range(p)], axis=dim)


# --------------------------------------------------------------------------
# 1-D Jigsaw (n-way generalization of the paper's 2-way scheme)
# --------------------------------------------------------------------------

def _local_matmul(x: jax.Array, w: jax.Array,
                  accum_dtype: Optional[jnp.dtype],
                  kernel: str = "xla") -> jax.Array:
    """x: [..., d_local], w: [m, d_local] -> [..., m] (partial sum).

    ``kernel="pallas"`` routes through the MXU-tiled blocked GEMM
    (kernels/ops.matmul: f32 VMEM accumulation, custom VJP); the result
    comes back in x.dtype, which is what every caller reduces in anyway.
    """
    if kernel == "pallas":
        from repro.kernels import ops
        return ops.matmul_nd(x, w, None, epilogue="none")
    out = jax.lax.dot_general(
        x, w, (((x.ndim - 1,), (1,)), ((), ())),
        preferred_element_type=accum_dtype or x.dtype)
    return out


def ring_matmul_chunked(x: jax.Array, w: jax.Array, *, axis_name: str,
                        axis_size: int,
                        accum_dtype: Optional[jnp.dtype] = jnp.float32,
                        kernel: str = "xla") -> jax.Array:
    """Chunk-granular fused compute/communication ring (paper §4).

    Instead of one local GEMM followed by a reduce-scatter of its output
    (``ring``/``rs``), the local weight block w [m, d/p] is split into p
    output-chunks of m/p rows and chunk j's GEMM is computed immediately
    before hop j's ppermute.  The schedule visits exactly the chunk order
    of ``ring_reduce_scatter``, so the result is bit-identical; the
    difference is that each hop's send is issued while the *next* chunk's
    GEMM is still pending, giving XLA (and the ICI DMA engines) a
    dependency graph in which communication overlaps computation -- the
    paper's "each hop's send overlaps the next chunk's compute".

    Wire format is ``x.dtype`` (bf16 compute halves per-hop bytes); the
    hop-to-hop adds run in ``accum_dtype`` -- the same cast points as
    ``ring_reduce_scatter``, so ring_chunked == ring stays bit-identical
    under every precision policy.
    """
    p = axis_size
    if p == 1:
        return _local_matmul(x, w, accum_dtype, kernel).astype(x.dtype)
    m = w.shape[0]
    if m % p != 0:
        raise ValueError(
            f"ring_matmul_chunked: out dim {m} not divisible by {p}")
    chunk = m // p
    idx = jax.lax.axis_index(axis_name)
    acc_dt = accum_dtype or x.dtype

    def chunk_mm(j):
        # GEMM of one output-chunk: x @ w[j*chunk:(j+1)*chunk].T -- cast
        # to the compute (wire) dtype first, exactly like the monolithic
        # ring's partial_sum, then up to the accumulation dtype.
        wj = jax.lax.dynamic_slice_in_dim(w, j * chunk, chunk, axis=0)
        y = _local_matmul(x, wj, accum_dtype, kernel).astype(x.dtype)
        return y.astype(acc_dt)

    perm = [(i, (i + 1) % p) for i in range(p)]
    # Same walk as ring_reduce_scatter: start with the chunk destined for
    # our successor; after p-1 hop+compute steps the accumulator is chunk
    # ``idx`` of the global sum.
    acc = chunk_mm((idx + p - 1) % p)
    for s in range(p - 1):
        acc = jax.lax.ppermute(acc.astype(x.dtype), axis_name, perm)
        acc = acc.astype(acc_dt) + chunk_mm((idx - 2 - s) % p)
    return acc.astype(x.dtype)


def jigsaw_matmul_1d(x: jax.Array, w: jax.Array, *, axis_name: str,
                     axis_size: int, impl: str = "rs",
                     accum_dtype: Optional[jnp.dtype] = jnp.float32,
                     kernel: str = "xla") -> jax.Array:
    """Manual (inside-shard_map) 1-D Jigsaw matmul.

    x: local [..., d/p] block; w: local [m, d/p] block.
    Returns the local [..., m/p] block of ``X @ W.T``.
    """
    if impl == "ring_chunked":
        return ring_matmul_chunked(
            x, w, axis_name=axis_name, axis_size=axis_size,
            accum_dtype=accum_dtype, kernel=kernel).astype(x.dtype)
    partial_sum = _local_matmul(x, w, accum_dtype, kernel)
    # reduce in the compute dtype: halves collective bytes (and the
    # transposed allgather in backward) at negligible accuracy cost
    partial_sum = partial_sum.astype(x.dtype)
    if impl == "ring":
        out = ring_reduce_scatter(partial_sum, axis_name, axis_size,
                                  accum_dtype=accum_dtype)
    elif impl == "rs":
        out = jax.lax.psum_scatter(partial_sum, axis_name,
                                   scatter_dimension=partial_sum.ndim - 1,
                                   tiled=True)
    elif impl == "allreduce":
        # Megatron-style completion (for comparison): full allreduce, then
        # slice our chunk.  2x the bytes of reduce-scatter + result is
        # materialized fully on every rank before slicing.
        full = jax.lax.psum(partial_sum, axis_name)
        p = axis_size
        chunk = full.shape[-1] // p
        idx = jax.lax.axis_index(axis_name)
        out = jax.lax.dynamic_slice_in_dim(full, idx * chunk, chunk, axis=-1)
    else:
        raise ValueError(f"unknown 1-D jigsaw impl {impl!r}")
    return out.astype(x.dtype)


def _present_batch_axes(mesh, rules: ShardingRules):
    return tuple(a for a in rules.batch_axes if a in mesh.shape)


def _cast_operands(x, w, b, compute_dtype):
    """Cast a linear's operands to the policy compute dtype (the block-
    boundary cast: params stored in param_dtype, GEMMs + collectives run
    in compute_dtype).  No-ops when dtypes already match."""
    if compute_dtype is None:
        return x, w, b
    cd = jnp.dtype(compute_dtype)
    return (x.astype(cd), w.astype(cd),
            None if b is None else b.astype(cd))


def _gspmd_pallas_dot(x: jax.Array, w: jax.Array, mesh,
                      rules: ShardingRules) -> jax.Array:
    """Dense ``x @ w.T`` on the Pallas GEMM under GSPMD sharding.

    Manual only over the batch axes (the model axes stay with GSPMD): at
    the region boundary GSPMD allgathers x's channel shards / w's blocks,
    the local GEMM runs ops.matmul_nd, and the caller's ``constrain``
    re-shards the output.  Used by the gspmd / p==1 / uneven fallback so
    ``kernel="pallas"`` is honoured there too.
    """
    from repro.kernels import ops
    batch_axes = _present_batch_axes(mesh, rules)
    dp = 1
    for a in batch_axes:
        dp *= mesh.shape[a]
    shard_batch = (x.ndim >= 2 and dp > 1 and x.shape[0] % dp == 0)
    if not shard_batch:
        # no data axes in play (single device / replicated batch): the
        # local GEMM IS the global GEMM.
        return ops.matmul_nd(x, w, None, epilogue="none")
    xdims: list = [None] * x.ndim
    xdims[0] = batch_axes
    xspec = P(*xdims)

    def fn(xl, wl):
        return ops.matmul_nd(xl, wl, None, epilogue="none")

    return shard_map(fn, mesh=mesh, in_specs=(xspec, P(None, None)),
                     out_specs=xspec, axis_names=set(batch_axes),
                     check_vma=False)(x, w)


def jigsaw_linear(x: jax.Array, w: jax.Array, b: Optional[jax.Array] = None,
                  *, rules: ShardingRules, mesh=None, impl: str = "rs",
                  accum_dtype: Optional[jnp.dtype] = jnp.float32,
                  w_data_sharded: bool = False,
                  kernel: str = "xla",
                  compute_dtype: Optional[jnp.dtype] = None) -> jax.Array:
    """Public 1-D Jigsaw linear: ``y = x @ w.T (+ b)``.

    Layouts (global view):
      x: [B, ..., d]  batch on the data axes, d on the tp axis -- zero
                      activation redundancy (domain parallelism),
      w: [m, d]       d (contracting) on the tp axis -- zero weight
                      redundancy; optionally m over the data axis too
                      (``w_data_sharded``: the FSDP-hybrid for >16-GB/chip
                      archs -- w is ring-allgathered over data inside),
      y: [B, ..., m]  same layout as x: layers compose with no resharding.

    The shard_map is *fully manual* over every mesh axis it touches --
    partially-auto shard_map replicates inputs over unmentioned axes,
    which would allgather the global batch on every linear.
    ``impl='gspmd'`` skips the explicit collectives entirely (sharding
    constraints only; beyond-paper comparison).
    """
    x, w, b = _cast_operands(x, w, b, compute_dtype)
    tp = rules.tp_axis
    if mesh is None:
        mesh = get_abstract_mesh()
    p = mesh.shape[tp] if tp in mesh.shape else 1

    # Uneven shapes cannot ride the explicit shard_map collectives (even
    # block division required); GSPMD pads such cases transparently.
    uneven = (x.shape[-1] % p != 0) or (w.shape[0] % p != 0) \
        or (w.shape[1] % p != 0)
    if impl == "gspmd" or p == 1 or uneven:
        if kernel == "pallas":
            # A pallas_call is an opaque custom call GSPMD cannot
            # partition THROUGH, so the dense dot rides a shard_map that
            # is manual over the batch axes only: GSPMD places the
            # gather/reshard collectives at the region boundary and the
            # local GEMM itself runs the MXU-tiled kernel -- the knob is
            # honoured instead of silently ignored.
            y = _gspmd_pallas_dot(x, w, mesh, rules)
        else:
            y = jax.lax.dot_general(
                x, w, (((x.ndim - 1,), (1,)), ((), ())),
                preferred_element_type=accum_dtype or x.dtype
            ).astype(x.dtype)
        y = constrain(y, rules.act(y.ndim))
        if b is not None:
            y = y + b
        return y

    batch_axes = _present_batch_axes(mesh, rules)
    dp = 1
    for a in batch_axes:
        dp *= mesh.shape[a]
    shard_batch = (x.ndim >= 2 and dp > 1 and x.shape[0] % dp == 0)
    # data axis carrying FSDP weight shards (last batch axis by convention)
    fsdp_axis = batch_axes[-1] if (w_data_sharded and batch_axes) else None
    fsdp_ok = (fsdp_axis is not None
               and w.shape[0] % mesh.shape[fsdp_axis] == 0)

    # Always fully-manual over the batch axes too: partially-auto
    # shard_map both replicates inputs over unmentioned axes AND trips an
    # XLA SPMD crash ("Invalid binary instruction opcode copy") at
    # 512 devices.  Non-divisible batch (e.g. long_500k's B=1) simply
    # stays replicated (spec entry None) inside the manual region.
    manual = {tp} | set(batch_axes)

    xdims: list = [None] * x.ndim
    if shard_batch:
        xdims[0] = batch_axes
    xdims[-1] = tp
    xspec = P(*xdims)
    wspec = P(fsdp_axis if fsdp_ok else None, tp)
    ospec = xspec

    def fn(xl, wl):
        if fsdp_ok:
            # FSDP-hybrid: gather the out-dim weight shards over data.
            wl = jax.lax.all_gather(wl, fsdp_axis, axis=0, tiled=True)
        return jigsaw_matmul_1d(xl, wl, axis_name=tp, axis_size=p,
                                impl=impl, accum_dtype=accum_dtype,
                                kernel=kernel)

    # check_vma=False: with B=1 (long_500k) the batch stays replicated
    # and VMA inference cannot see through the FSDP all_gather; the
    # equivalence tests (tests/dist_scenarios.py) cover correctness.
    y = shard_map(fn, mesh=mesh, in_specs=(xspec, wspec),
                      out_specs=ospec, axis_names=manual,
                      check_vma=False)(x, w)
    if b is not None:
        y = y + b  # b: [m] sharded on tp -> local add, no comm.
    return y


# --------------------------------------------------------------------------
# 2-D Jigsaw (p x q generalization of the paper's 4-way scheme): Cannon
# --------------------------------------------------------------------------

def _skew(x: jax.Array, amount: jax.Array, axis_name: str, q: int
          ) -> jax.Array:
    """Rotate ``x`` along mesh axis ``axis_name`` by ``amount`` positions
    (towards lower rank), where ``amount`` is a per-rank traced scalar
    (its row/col index).  ppermute applies one static shift; we apply q-1
    conditional shifts so row r accepts exactly r of them."""
    perm = [(i, (i - 1) % q) for i in range(q)]
    for s in range(q - 1):
        shifted = jax.lax.ppermute(x, axis_name, perm)
        x = jnp.where(s < amount, shifted, x)
    return x


def jigsaw_matmul_2d(x: jax.Array, w: jax.Array, *, dom_axis: str,
                     tp_axis: str, dom_size: int, tp_size: int,
                     accum_dtype: Optional[jnp.dtype] = jnp.float32,
                     kernel: str = "xla") -> jax.Array:
    """Manual (inside-shard_map) 2-D Jigsaw matmul via Cannon's algorithm.

    Global math: Y[n, m] = X[n, d] @ W[m, d].T on a (dom=p) x (tp=q) grid
    with p == q (Cannon requires a square grid; the paper's 4-way is the
    2x2 instance).

    Local blocks at grid position (i=dom, j=tp):
      x: [..., n/p, d/q]   block X(i, j)
      w: [m/q, d/p]        block W(m-block j, d-block i)   (transposed
                           Cannon layout -- this is what lets both operands
                           travel along a single mesh axis each)
      y: [..., n/p, m/q]   block Y(i, j)

    Schedule: skew X left by i along tp, skew W up by j along dom, then q
    multiply-accumulate steps, rotating X left and W up by one between
    steps.  Zero redundancy: each rank only ever buffers one remote block
    (the paper's "necessary buffers for communication").
    """
    if dom_size != tp_size:
        raise ValueError(f"2-D Jigsaw needs a square grid, got "
                         f"{dom_size}x{tp_size}")
    q = tp_size
    i = jax.lax.axis_index(dom_axis)
    j = jax.lax.axis_index(tp_axis)

    def mm(a, b):
        # Same [..., k] x [n, k] contraction as the 1-D local block, so
        # the Cannon multiply-accumulate steps ride the kernel knob too.
        # The pallas kernel returns x.dtype (its f32 accumulation is
        # internal); cast back up so the q cross-step partial sums
        # accumulate in accum_dtype on both engines.
        out = _local_matmul(a, b, accum_dtype, kernel)
        return out.astype(accum_dtype) if accum_dtype else out

    a = _skew(x, i, tp_axis, q)     # now holds X(i, (j+i) % q)
    bm = _skew(w, j, dom_axis, q)   # now holds W(j, (i+j) % q)
    acc = mm(a, bm)
    perm_t = [(t, (t - 1) % q) for t in range(q)]
    for _ in range(q - 1):
        a = jax.lax.ppermute(a, tp_axis, perm_t)
        bm = jax.lax.ppermute(bm, dom_axis, perm_t)
        acc = acc + mm(a, bm)
    return acc


def jigsaw_linear_2d(x: jax.Array, w: jax.Array,
                     b: Optional[jax.Array] = None, *, rules: ShardingRules,
                     mesh=None, domain_dim: int = -2,
                     accum_dtype: Optional[jnp.dtype] = jnp.float32,
                     kernel: str = "xla",
                     compute_dtype: Optional[jnp.dtype] = None) -> jax.Array:
    """Public 2-D Jigsaw linear (paper's 4-way, generalized).

    Global layouts:
      x: [..., n, d]  n on ``mdom``, d on ``mtp``
      w: [m, d]       m on ``mtp``,  d on ``mdom``   (Cannon layout)
      y: [..., n, m]  n on ``mdom``, m on ``mtp``  -- same as x: composable.

    Cannon rotates the OPERAND blocks, so the wire format is simply the
    (policy-cast) operand dtype -- bf16 compute halves the skew/rotate
    bytes; the q-step accumulator stays in ``accum_dtype``.
    """
    if not rules.is_2d:
        raise ValueError("jigsaw_linear_2d requires 2-D ShardingRules")
    x, w, b = _cast_operands(x, w, b, compute_dtype)
    dom, tp = rules.dom_axis, rules.tp_axis
    if mesh is None:
        mesh = get_abstract_mesh()
    p, q = mesh.shape[dom], mesh.shape[tp]

    batch_axes = _present_batch_axes(mesh, rules)
    dp = 1
    for a in batch_axes:
        dp *= mesh.shape[a]
    shard_batch = (dp > 1 and x.shape[0] % dp == 0)

    nd = x.ndim
    ddim = domain_dim % nd
    xdims: list = [None] * nd
    if shard_batch and ddim != 0:
        xdims[0] = batch_axes
    xdims[ddim] = dom
    xdims[nd - 1] = tp
    xspec = P(*xdims)
    wspec = P(tp, dom)
    ospec = xspec
    manual = {dom, tp} | set(batch_axes)

    fn = partial(jigsaw_matmul_2d, dom_axis=dom, tp_axis=tp, dom_size=p,
                 tp_size=q, accum_dtype=accum_dtype, kernel=kernel)
    y = shard_map(fn, mesh=mesh, in_specs=(xspec, wspec),
                      out_specs=ospec, axis_names=manual,
                      check_vma=False)(x, w)
    y = y.astype(x.dtype)
    if b is not None:
        y = y + b
    return y


def jigsaw_matmul_2d_t(x: jax.Array, w: jax.Array, *, dom_axis: str,
                       tp_axis: str, dom_size: int, tp_size: int,
                       accum_dtype: Optional[jnp.dtype] = jnp.float32,
                       kernel: str = "xla") -> jax.Array:
    """Manual 2-D Jigsaw *transposed* matmul: ``Y = W @ X`` contracting
    X's second-to-last dim.  This is the paper's "transposed MLP" trick
    (§5: implement ``X^T W`` directly instead of transposing) used by the
    WeatherMixer token-mixing MLP: the token dim is contracted *in place*
    with a different communication pattern instead of materializing a
    transpose.

    Local blocks at grid position (i=dom, j=tp):
      x: [..., t/p, c/q]   block X(i, j)     (t = tokens, c = channels)
      w: [m/p, t/q]        block W(m-block i, t-block j)  (natural layout)
      y: [..., m/p, c/q]   block Y(i, j)

    Classic Cannon: skew W left by i along tp, skew X up by j along dom;
    q multiply-accumulate steps rotating W left / X up.

    ``kernel="pallas"`` lowers each multiply-accumulate step to the fused
    ``acc + w @ x`` MXU kernel (kernels/cannon.cannon_t_step; one
    pallas_call per step, f32 VMEM accumulation).
    """
    if dom_size != tp_size:
        raise ValueError(f"2-D Jigsaw needs a square grid, got "
                         f"{dom_size}x{tp_size}")
    q = tp_size
    i = jax.lax.axis_index(dom_axis)
    j = jax.lax.axis_index(tp_axis)

    if kernel == "pallas":
        from repro.kernels import cannon
        wl = _skew(w, i, tp_axis, q)    # W(i, (j+i) % q)
        xl = _skew(x, j, dom_axis, q)   # X((i+j) % q, j)
        return cannon.cannon_t_loop(wl, xl, dom_axis=dom_axis,
                                    tp_axis=tp_axis, q=q,
                                    accum_dtype=accum_dtype)

    def mm(wb, xb):
        # wb: [m_l, t_l]; xb: [..., t_l, c_l] -> [..., m_l, c_l]
        out = jax.lax.dot_general(
            wb, xb, (((1,), (xb.ndim - 2,)), ((), ())),
            preferred_element_type=accum_dtype or xb.dtype)
        # dot_general puts wb's free dim first: [m_l, ..., c_l] -> move it.
        return jnp.moveaxis(out, 0, -2)

    wl = _skew(w, i, tp_axis, q)    # now W(i, (j+i) % q)
    xl = _skew(x, j, dom_axis, q)   # now X((i+j) % q, j)
    acc = mm(wl, xl)
    perm_t = [(t, (t - 1) % q) for t in range(q)]
    for _ in range(q - 1):
        wl = jax.lax.ppermute(wl, tp_axis, perm_t)
        xl = jax.lax.ppermute(xl, dom_axis, perm_t)
        acc = acc + mm(wl, xl)
    return acc


def jigsaw_linear_2d_t(x: jax.Array, w: jax.Array,
                       b: Optional[jax.Array] = None, *,
                       rules: ShardingRules, mesh=None,
                       accum_dtype: Optional[jnp.dtype] = jnp.float32,
                       kernel: str = "xla",
                       compute_dtype: Optional[jnp.dtype] = None
                       ) -> jax.Array:
    """Public 2-D Jigsaw transposed linear: ``y[..., m, c] = w[m, t] @
    x[..., t, c] (+ b[:, None])``.

    Global layouts:
      x: [..., t, c]  t on ``mdom``, c on ``mtp``
      w: [m, t]       m on ``mdom``, t on ``mtp``
      y: [..., m, c]  m on ``mdom``, c on ``mtp``  -- same as x: composable.

    ``kernel="pallas"``: the Cannon multiply-accumulate steps run the
    fused ``acc + w @ x`` MXU kernel (one pallas_call per step) instead
    of dot_general.
    """
    if not rules.is_2d:
        raise ValueError("jigsaw_linear_2d_t requires 2-D ShardingRules")
    x, w, b = _cast_operands(x, w, b, compute_dtype)
    dom, tp = rules.dom_axis, rules.tp_axis
    if mesh is None:
        mesh = get_abstract_mesh()
    p, q = mesh.shape[dom], mesh.shape[tp]

    batch_axes = _present_batch_axes(mesh, rules)
    dp = 1
    for a in batch_axes:
        dp *= mesh.shape[a]
    shard_batch = (x.ndim > 2 and dp > 1 and x.shape[0] % dp == 0)

    nd = x.ndim
    xdims: list = [None] * nd
    if shard_batch:
        xdims[0] = batch_axes
    xdims[nd - 2] = dom
    xdims[nd - 1] = tp
    xspec = P(*xdims)
    wspec = P(dom, tp)
    ospec = xspec
    manual = {dom, tp} | set(batch_axes)

    fn = partial(jigsaw_matmul_2d_t, dom_axis=dom, tp_axis=tp, dom_size=p,
                 tp_size=q, accum_dtype=accum_dtype, kernel=kernel)
    y = shard_map(fn, mesh=mesh, in_specs=(xspec, wspec),
                      out_specs=ospec, axis_names=manual,
                      check_vma=False)(x, w)
    y = y.astype(x.dtype)
    if b is not None:
        y = y + b[:, None]
    return y


# --------------------------------------------------------------------------
# Analytic communication volume (telemetry/accounting.py's cost model)
# --------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class CommVolume:
    """Bytes sent per device for one linear layer's forward pass."""
    scheme: str
    bytes_per_device: float

def comm_volume_jigsaw_1d(tokens: int, m: int, p: int, dtype_bytes: int = 2
                          ) -> CommVolume:
    # ring reduce-scatter of [tokens, m]: (p-1) chunks of tokens*m/p each.
    return CommVolume("jigsaw-1d", (p - 1) / p * tokens * m * dtype_bytes)

@dataclasses.dataclass(frozen=True)
class CommSchedule:
    """Per-hop accounting of an explicit ring schedule (one linear fwd).

    ``flops_per_hop`` is the local GEMM work the schedule exposes
    *between* consecutive sends -- the compute available to hide each
    hop.  The monolithic ``ring`` finishes its single GEMM before hop 0,
    so it exposes zero overlappable work; ``ring_chunked`` exposes one
    output-chunk GEMM per hop (the paper's overlap).
    """
    scheme: str
    hops: int
    bytes_per_hop: float
    flops_per_hop: float
    bytes_per_device: float


def comm_schedule_jigsaw_1d(tokens: int, m: int, d_local: int, p: int,
                            dtype_bytes: int = 2,
                            impl: str = "ring_chunked") -> CommSchedule:
    """Hop-level schedule of the explicit 1-D Jigsaw ring.

    Both schedules move the same (p-1)/p * tokens * m bytes per device;
    they differ in what compute is still pending while each hop's send
    is in flight:

      ring         : nothing (the single GEMM finished before hop 0),
      ring_chunked : one output-chunk GEMM (2 * tokens * d_local * m/p
                     flops) -- *exposed to* XLA's scheduler, overlap
                     best-effort.
    """
    if impl not in ("ring", "ring_chunked"):
        raise ValueError(f"comm_schedule_jigsaw_1d: unknown impl {impl!r}")
    hop_bytes = tokens * (m / p) * dtype_bytes
    flops = {"ring": 0.0,
             "ring_chunked": 2.0 * tokens * d_local * (m / p)}[impl]
    return CommSchedule(
        scheme="jigsaw-1d-" + impl,
        hops=p - 1, bytes_per_hop=hop_bytes,
        flops_per_hop=flops,
        bytes_per_device=(p - 1) * hop_bytes)


def comm_volume_jigsaw_2d(tokens: int, m: int, q: int, dtype_bytes: int = 2
                          ) -> CommVolume:
    # Cannon on q x q grid: per step each rank forwards its X block
    # [tokens/q, d/q] and W block [m/q, d/q]; 2(q-1) block sends + skews.
    # Expressed in output-proportional terms for comparability.
    blk = tokens / q * m / q
    return CommVolume("jigsaw-2d", 2 * (q - 1) * blk * dtype_bytes)
