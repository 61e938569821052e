"""One-kernel ring: the paper's §4 schedule as a single ``pallas_call``.

``impl="ring_chunked"`` (core/jigsaw.py) interleaves per-chunk GEMMs with
``ppermute`` hops, but GEMMs and collectives remain *separate HLOs* -- the
overlap is whatever XLA's scheduler decides.  This module closes that gap:
the whole p-step schedule -- chunk GEMM, hop add, remote send -- runs inside
ONE ``pallas_call`` per ring, so hop *h*'s DMA is guaranteed in flight while
chunk *h+1*'s MXU GEMM executes (DESIGN.md §11).

Layout (inside the 1-D Jigsaw shard_map; see ``jigsaw_matmul_1d``):
  x: [..., d/p] local activation block     w: [m, d/p] local weight block
  out: [..., m/p] -- rank r's chunk of ``X @ W.T`` (reduce-scattered).

Schedule (grid step ``s`` on rank ``my``, p = ring size):
  * compute chunk ``j_s = (my - 1 - s) % p``'s GEMM; the w-chunk BlockSpec
    index_map walks that order, so the grid pipeline's double-buffered
    operand fetch IS the paper's chunk prefetch,
  * add the partial sum that arrived on hop ``s-1`` (``accum_dtype``),
  * cast down to the wire dtype (``x.dtype``) and start hop ``s``'s
    ``make_async_remote_copy`` to the ring successor -- the DMA flies
    while step ``s+1``'s GEMM runs.
The cast points (wire = x.dtype, hop adds in accum_dtype) are exactly
``ring_reduce_scatter``'s, so ``ring_fused == ring`` stays bit-identical
under every precision policy.

Deterministic fallback (CPU / interpret mode / VMEM-guard trips): the same
schedule lowered to chunk-granular GEMMs (honouring ``kernel=``, i.e. the
MXU-tiled ops.matmul in interpret mode) interleaved with ``ppermute`` --
semantically ``ring_matmul_chunked``, bit-identical to ``ring``, so parity
tests run everywhere.  What the fallback does NOT prove: the RDMA slot
discipline and in-kernel overlap of the TPU path (hardware-only).

Backward = the transposed schedule: the cotangent of a reduce-scattered
output is its ring ALLGATHER (rank-ordered); the fallback then runs the
monolithic local backward GEMMs via ``jax.vjp``, which reproduces
AD-of-``ring`` bit-for-bit (every wire cast round-trips losslessly and the
chunk scatter is disjoint).  On TPU the same fused kernel runs with the
transposed schedule: dy chunks ride the ring, each hop's arrival feeds a
dw-chunk GEMM while dx accumulates in f32 (reduction order over the m dim
differs from XLA AD there -- TPU-only, documented in DESIGN.md §11).

Also here: the Pallas transposed-Cannon step kernel (``cannon_t_step``)
used by ``jigsaw_matmul_2d_t`` under ``kernel="pallas"`` -- fused
``acc + w @ x`` multiply-accumulate with f32 VMEM accumulation and a
custom VJP whose backward GEMMs run the same machinery -- plus the fused
q-hop TPU variant where the rotate steps are in-kernel remote copies.
"""
from __future__ import annotations

import functools
import math
import warnings
from typing import Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

try:  # pltpu imports cleanly on CPU builds of jax; guard anyway.
    from jax.experimental.pallas import tpu as pltpu
except ImportError:  # pragma: no cover - exotic builds only
    pltpu = None

from repro.kernels import ops
from repro.kernels.block_matmul import sublane
from repro.telemetry.spans import get_tracer

# Per-core VMEM we allow the fused kernel to pin (16 MB on v4/v5 cores,
# minus headroom for the pipeline's own double buffers).
VMEM_BUDGET_BYTES = 12 * 1024 * 1024

_WARNED: set = set()


def _warn_once(key, msg: str) -> None:
    if key not in _WARNED:
        _WARNED.add(key)
        warnings.warn(msg, stacklevel=3)


# --------------------------------------------------------------------------
# VMEM-budget guard + path selection
# --------------------------------------------------------------------------

def ring_footprint_bytes(rows: int, d_local: int, m: int, p: int,
                         x_dtype, accum_dtype) -> int:
    """VMEM bytes the fused forward kernel pins for one ring.

    x block + double-buffered w chunk (the grid pipeline keeps two) +
    send/recv ring buffers (2 slots each, wire dtype) + the in-flight hop
    accumulator + the output chunk.
    """
    wire = jnp.dtype(x_dtype).itemsize
    acc = jnp.dtype(accum_dtype).itemsize if accum_dtype else wire
    mc = max(m // max(p, 1), 1)
    return int(rows * d_local * wire            # x block (resident)
               + 2 * mc * d_local * wire        # w chunk, double-buffered
               + 4 * rows * mc * wire           # send/recv bufs, 2 slots each
               + rows * mc * max(acc, 4)        # hop accumulator
               + rows * mc * wire)              # output chunk


def fits_vmem(rows: int, d_local: int, m: int, p: int, x_dtype,
              accum_dtype, budget: Optional[int] = None) -> bool:
    budget = VMEM_BUDGET_BYTES if budget is None else budget
    return ring_footprint_bytes(rows, d_local, m, p, x_dtype,
                                accum_dtype) <= budget


def _select_path(rows: int, d_local: int, m: int, p: int, x_dtype,
                 accum_dtype, mesh_axes: Optional[Sequence[str]],
                 axis_name: str, backend: Optional[str] = None,
                 budget: Optional[int] = None) -> str:
    """Choose ``"tpu"`` (single fused pallas_call) or ``"fallback"``
    (chunk-granular schedule).  Parameterized on ``backend``/``budget`` so
    the guard logic itself is testable on CPU."""
    backend = backend or jax.default_backend()
    if backend != "tpu" or pltpu is None:
        path = "fallback"
    elif mesh_axes is None or axis_name not in mesh_axes:
        # Neighbour addressing needs every mesh axis's coordinate; a
        # partially-manual mesh (or a caller that didn't thread the axis
        # names) cannot build them.
        _warn_once(("axes", axis_name,
                    None if mesh_axes is None else tuple(mesh_axes)),
                   "fused_ring: cannot address ring neighbours (mesh axes "
                   f"unavailable for ring {axis_name!r}); falling back to "
                   "the chunk-granular ring_chunked schedule")
        path = "fallback"
    elif not fits_vmem(rows, d_local, m, p, x_dtype, accum_dtype,
                       budget=budget):
        fp = ring_footprint_bytes(rows, d_local, m, p, x_dtype, accum_dtype)
        _warn_once(("vmem", rows, d_local, m, p),
                   f"fused_ring: chunk tiles need ~{fp / 2**20:.1f} MiB "
                   "VMEM > budget; falling back to the chunk-granular "
                   "ring_chunked schedule")
        path = "fallback"
    else:
        path = "tpu"
    # counted at trace time on the process tracer, so a run can say which
    # schedule its kernels took
    get_tracer().counter(f"fused_ring.ring.{path}")
    return path


# --------------------------------------------------------------------------
# Shared helpers (kernels-local so core -> kernels stays one-way)
# --------------------------------------------------------------------------

def _local_mm(x: jax.Array, w: jax.Array, accum_dtype, kernel: str
              ) -> jax.Array:
    """x [..., k] x w [m, k] -> [..., m]; mirrors jigsaw._local_matmul so
    the fallback honours the ``kernel=`` knob with identical numerics."""
    if kernel == "pallas":
        return ops.matmul_nd(x, w, None, epilogue="none")
    return jax.lax.dot_general(
        x, w, (((x.ndim - 1,), (1,)), ((), ())),
        preferred_element_type=accum_dtype or x.dtype)


def _rank_order_all_gather(x: jax.Array, axis_name: str, p: int
                           ) -> jax.Array:
    """The backward ring: ring allgather of the output cotangent, reordered
    into rank order -- the transpose of the forward reduce-scatter.  Every
    hop ships dy.dtype bytes (same wire format as forward)."""
    if p == 1:
        return x
    idx = jax.lax.axis_index(axis_name)
    perm = [(i, (i + 1) % p) for i in range(p)]
    pieces = [x]
    cur = x
    for _ in range(p - 1):
        cur = jax.lax.ppermute(cur, axis_name, perm)
        pieces.append(cur)
    # piece t originated at rank (idx - t) % p; reorder to rank order.
    stacked = jnp.stack(pieces, axis=0)
    order = (idx - jnp.arange(p, dtype=jnp.int32)) % p
    inv = jnp.zeros((p,), jnp.int32).at[order].set(
        jnp.arange(p, dtype=jnp.int32))
    stacked = jnp.take(stacked, inv, axis=0)
    return jnp.concatenate([stacked[j] for j in range(p)], axis=-1)


def _ring_neighbors(axis_name: str, p: int,
                    mesh_axes: Optional[Sequence[str]]):
    """(succ_id, pred_id, device_id_type) for the ring RDMA.

    Neighbours are addressed by full MESH coordinates built from the
    manual axis indices (``mesh_axes`` = mesh.axis_names threaded down
    from jigsaw_linear), replacing the ring axis's coordinate.  A bare
    ring position is not a device id: the TPU compiler takes LOGICAL ids
    as scalars only.
    """
    my = jax.lax.axis_index(axis_name)
    mesh_axes = (axis_name,) if mesh_axes is None else mesh_axes
    coords = [jax.lax.axis_index(a) for a in mesh_axes]
    k = list(mesh_axes).index(axis_name)
    succ = list(coords)
    pred = list(coords)
    succ[k] = (my + 1) % p
    pred[k] = (my - 1) % p
    return tuple(succ), tuple(pred), pltpu.DeviceIdType.MESH


# --------------------------------------------------------------------------
# TPU forward kernel: the fused multi-hop ring
# --------------------------------------------------------------------------
#
# RDMA slot discipline (hop h, double-buffered):
#   src = send_buf[h % 2] (mine) -> dst = recv_buf[h % 2] (successor's).
# Safety of reusing slots every other hop:
#   * my send_buf[h % 2] is rewritten at step h; its previous use was hop
#     h-2's send, whose completion was waited at step h-1 (hop(h-1).wait()
#     covers my send sem);
#   * my hop-h payload lands in the successor's recv_buf[h % 2], whose
#     previous content (hop h-2) they consumed at their step h-1 BEFORE
#     starting their hop h-1 send; my hop-h start happens-after I received
#     their hop h-1, hence after that consumption.  No credits needed.

def _ring_fwd_kernel(idx_ref, x_ref, w_ref, o_ref,
                     send_buf, recv_buf, send_sem, recv_sem, *,
                     p: int, acc_dtype, mesh_axes, axis_name):
    s = pl.program_id(0)
    wire = o_ref.dtype
    # Chunk GEMM for this grid step.  w_ref is already chunk
    # (my - 1 - s) % p: the BlockSpec index_map walks the ring order, so
    # Pallas' pipelined operand fetch double-buffers the chunk loads.
    # The MXU accumulates in f32 natively; the wire round-trip below puts
    # the cast points exactly where ring_reduce_scatter has them.
    y = jax.lax.dot_general(
        x_ref[...], w_ref[...], (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32)
    y = y.astype(wire).astype(acc_dtype)

    if p == 1:
        o_ref[...] = y.astype(wire)
        return

    succ, pred, id_type = _ring_neighbors(axis_name, p, mesh_axes)

    def hop(h):
        return pltpu.make_async_remote_copy(
            src_ref=send_buf.at[h % 2], dst_ref=recv_buf.at[h % 2],
            send_sem=send_sem.at[h % 2], recv_sem=recv_sem.at[h % 2],
            device_id=succ, device_id_type=id_type)

    @pl.when(s == 0)
    def _first():
        # Neighbour barrier: no RDMA until both neighbours entered the
        # kernel (their buffers exist); required before the first remote
        # DMA of a collective kernel.
        barrier = pltpu.get_barrier_semaphore()
        pltpu.semaphore_signal(barrier, 1, device_id=succ,
                               device_id_type=id_type)
        pltpu.semaphore_signal(barrier, 1, device_id=pred,
                               device_id_type=id_type)
        pltpu.semaphore_wait(barrier, 2)
        send_buf[0] = y.astype(wire)
        hop(0).start()

    @pl.when(jnp.logical_and(s > 0, s < p - 1))
    def _mid():
        # hop(s-1).wait(): my hop s-1 send drained AND the predecessor's
        # hop s-1 payload arrived -- then fuse add + cast + next send,
        # all while step s+1's w chunk is already being fetched.
        hop(s - 1).wait()
        tot = recv_buf[(s - 1) % 2].astype(acc_dtype) + y
        send_buf[s % 2] = tot.astype(wire)
        hop(s).start()

    @pl.when(s == p - 1)
    def _last():
        hop(s - 1).wait()
        tot = recv_buf[(s - 1) % 2].astype(acc_dtype) + y
        o_ref[...] = tot.astype(wire)


def _ring_fwd_tpu(x: jax.Array, w: jax.Array, axis_name: str, p: int,
                  acc_dt, mesh_axes) -> jax.Array:
    lead = x.shape[:-1]
    rows = math.prod(lead) if lead else 1
    d_local = x.shape[-1]
    mc = w.shape[0] // p
    x2 = x.reshape(rows, d_local)
    my = jax.lax.axis_index(axis_name).astype(jnp.int32).reshape(1)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(p,),
        in_specs=[
            pl.BlockSpec((rows, d_local), lambda s, idx: (0, 0)),
            # chunk (my - 1 - s) % p: the ring walk order.
            pl.BlockSpec((mc, d_local),
                         lambda s, idx: ((idx[0] - 1 - s) % p, 0)),
        ],
        out_specs=pl.BlockSpec((rows, mc), lambda s, idx: (0, 0)),
        scratch_shapes=[
            pltpu.VMEM((2, rows, mc), x.dtype),   # send_buf
            pltpu.VMEM((2, rows, mc), x.dtype),   # recv_buf
            pltpu.SemaphoreType.DMA((2,)),
            pltpu.SemaphoreType.DMA((2,)),
        ],
    )
    out = pl.pallas_call(
        functools.partial(_ring_fwd_kernel, p=p,
                          acc_dtype=jnp.dtype(acc_dt),
                          mesh_axes=mesh_axes, axis_name=axis_name),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((rows, mc), x.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",), collective_id=0),
    )(my, x2, w)
    return out.reshape(lead + (mc,))


# --------------------------------------------------------------------------
# TPU backward kernel: the same ring, transposed schedule
# --------------------------------------------------------------------------

def _ring_bwd_kernel(idx_ref, x_ref, w_ref, dy_ref, dx_ref, dw_ref,
                     dx_acc, send_buf, recv_buf, send_sem, recv_sem, *,
                     p: int, mesh_axes, axis_name):
    """Transposed schedule: dy chunks ride the SAME ring (allgather
    direction); hop s's arrival is rank (my - s) % p's dy chunk, which
    feeds that chunk's dw GEMM (pipelined out BlockSpec) while dx
    accumulates across all p chunks in f32.  Same slot discipline as
    forward."""
    s = pl.program_id(0)

    if p == 1:
        cur = dy_ref[...]
    else:
        succ, pred, id_type = _ring_neighbors(axis_name, p, mesh_axes)

        def hop(h):
            return pltpu.make_async_remote_copy(
                src_ref=send_buf.at[h % 2], dst_ref=recv_buf.at[h % 2],
                send_sem=send_sem.at[h % 2], recv_sem=recv_sem.at[h % 2],
                device_id=succ, device_id_type=id_type)

        @pl.when(s == 0)
        def _first():
            barrier = pltpu.get_barrier_semaphore()
            pltpu.semaphore_signal(barrier, 1, device_id=succ,
                                   device_id_type=id_type)
            pltpu.semaphore_signal(barrier, 1, device_id=pred,
                                   device_id_type=id_type)
            pltpu.semaphore_wait(barrier, 2)
            send_buf[0] = dy_ref[...]
            hop(0).start()

        @pl.when(jnp.logical_and(s > 0, s < p - 1))
        def _mid():
            hop(s - 1).wait()
            send_buf[s % 2] = recv_buf[(s - 1) % 2]
            hop(s).start()

        @pl.when(s == p - 1)
        def _lastwait():
            hop(s - 1).wait()

        cur = jnp.where(s == 0, dy_ref[...], recv_buf[(s - 1) % 2])

    # dw chunk for rank (my - s) % p's rows (out BlockSpec walks them):
    # dw_j = dy_j^T @ x.
    dw_ref[...] = jax.lax.dot_general(
        cur, x_ref[...], (((0,), (0,)), ((), ())),
        preferred_element_type=jnp.float32).astype(dw_ref.dtype)
    # dx accumulates every chunk's contribution in f32 (reduction order
    # over m differs from XLA AD's monolithic dot -- TPU-only divergence,
    # DESIGN.md §11).
    @pl.when(s == 0)
    def _zero():
        dx_acc[...] = jnp.zeros_like(dx_acc)
    dx_acc[...] += jax.lax.dot_general(
        cur, w_ref[...], (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)
    @pl.when(s == p - 1)
    def _emit():
        dx_ref[...] = dx_acc[...].astype(dx_ref.dtype)


def _ring_bwd_tpu(x: jax.Array, w: jax.Array, dy: jax.Array,
                  axis_name: str, p: int, mesh_axes
                  ) -> Tuple[jax.Array, jax.Array]:
    lead = x.shape[:-1]
    rows = math.prod(lead) if lead else 1
    d_local = x.shape[-1]
    mc = w.shape[0] // p
    x2 = x.reshape(rows, d_local)
    dy2 = dy.reshape(rows, mc)
    my = jax.lax.axis_index(axis_name).astype(jnp.int32).reshape(1)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(p,),
        in_specs=[
            pl.BlockSpec((rows, d_local), lambda s, idx: (0, 0)),
            # w chunk for the dy chunk arriving at step s: (my - s) % p.
            pl.BlockSpec((mc, d_local),
                         lambda s, idx: ((idx[0] - s) % p, 0)),
            pl.BlockSpec((rows, mc), lambda s, idx: (0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((rows, d_local), lambda s, idx: (0, 0)),
            pl.BlockSpec((mc, d_local),
                         lambda s, idx: ((idx[0] - s) % p, 0)),
        ],
        scratch_shapes=[
            pltpu.VMEM((rows, d_local), jnp.float32),   # dx accumulator
            pltpu.VMEM((2, rows, mc), dy.dtype),        # send_buf
            pltpu.VMEM((2, rows, mc), dy.dtype),        # recv_buf
            pltpu.SemaphoreType.DMA((2,)),
            pltpu.SemaphoreType.DMA((2,)),
        ],
    )
    dx, dw = pl.pallas_call(
        functools.partial(_ring_bwd_kernel, p=p, mesh_axes=mesh_axes,
                          axis_name=axis_name),
        grid_spec=grid_spec,
        out_shape=[jax.ShapeDtypeStruct((rows, d_local), x.dtype),
                   jax.ShapeDtypeStruct(w.shape, w.dtype)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",), collective_id=1),
    )(my, x2, w, dy2)
    return dx.reshape(x.shape), dw


# --------------------------------------------------------------------------
# The fused ring op (custom VJP; called inside the Jigsaw shard_map)
# --------------------------------------------------------------------------

def _chunk_walk(x, w, axis_name, p, acc_dt, kernel):
    """Chunk-granular fallback schedule: GEMM chunk j right before hop j's
    ppermute -- ring_matmul_chunked's walk with identical cast points, so
    the fallback stays bit-identical to ``ring`` everywhere."""
    m = w.shape[0]
    if m % p != 0:
        raise ValueError(f"fused_ring: out dim {m} not divisible by {p}")
    chunk = m // p
    idx = jax.lax.axis_index(axis_name)

    def chunk_mm(j):
        wj = jax.lax.dynamic_slice_in_dim(w, j * chunk, chunk, axis=0)
        y = _local_mm(x, wj, acc_dt, kernel).astype(x.dtype)
        return y.astype(acc_dt)

    perm = [(i, (i + 1) % p) for i in range(p)]
    acc = chunk_mm((idx + p - 1) % p)
    for s in range(p - 1):
        acc = jax.lax.ppermute(acc.astype(x.dtype), axis_name, perm)
        acc = acc.astype(acc_dt) + chunk_mm((idx - 2 - s) % p)
    return acc.astype(x.dtype)


@functools.partial(jax.custom_vjp, nondiff_argnums=(2, 3, 4, 5, 6))
def _fused(x, w, axis_name, p, acc_name, kernel, mesh_axes):
    acc_dt = jnp.dtype(acc_name)
    if p == 1:
        return _local_mm(x, w, acc_dt, kernel).astype(x.dtype)
    lead = x.shape[:-1]
    rows = math.prod(lead) if lead else 1
    path = _select_path(rows, x.shape[-1], w.shape[0], p, x.dtype, acc_dt,
                        mesh_axes, axis_name)
    if path == "tpu":
        return _ring_fwd_tpu(x, w, axis_name, p, acc_dt, mesh_axes)
    return _chunk_walk(x, w, axis_name, p, acc_dt, kernel)


def _fused_fwd(x, w, axis_name, p, acc_name, kernel, mesh_axes):
    return _fused(x, w, axis_name, p, acc_name, kernel, mesh_axes), (x, w)


def _fused_bwd(axis_name, p, acc_name, kernel, mesh_axes, res, dy):
    x, w = res
    acc_dt = jnp.dtype(acc_name)
    lead = x.shape[:-1]
    rows = math.prod(lead) if lead else 1
    if p > 1 and _select_path(rows, x.shape[-1], w.shape[0], p, x.dtype,
                              acc_dt, mesh_axes, axis_name) == "tpu":
        return _ring_bwd_tpu(x, w, dy, axis_name, p, mesh_axes)
    # Transposed schedule, fallback form: gather the full cotangent (the
    # backward ring), then the monolithic local backward GEMMs.  This is
    # the exact program jax.grad builds for impl="ring" -- the allgather is
    # value-exact (disjoint chunks, lossless wire round-trips), so grads
    # are bit-identical to ``ring``'s.
    cot = _rank_order_all_gather(dy, axis_name, p)

    def primal(xx, ww):
        return _local_mm(xx, ww, acc_dt, kernel).astype(x.dtype)

    _, vjp = jax.vjp(primal, x, w)
    return vjp(cot)


_fused.defvjp(_fused_fwd, _fused_bwd)


def fused_ring_matmul(x: jax.Array, w: jax.Array, *, axis_name: str,
                      axis_size: int,
                      accum_dtype=jnp.float32, kernel: str = "xla",
                      mesh_axes: Optional[Sequence[str]] = None
                      ) -> jax.Array:
    """The one-kernel ring matmul (``impl="ring_fused"``).

    x: local [..., d/p]; w: local [m, d/p] -> local [..., m/p] chunk of
    ``X @ W.T``.  Must be called inside shard_map with ``axis_name``
    manual.  On TPU (and within the VMEM budget) the whole p-step
    schedule is one ``pallas_call``; elsewhere a deterministic
    chunk-granular fallback runs.  Both are bit-identical to ``ring``
    (forward AND grads) under fp32 and bf16 policies.

    ``mesh_axes``: the mesh's manual axis names in mesh order -- required
    by the TPU path to address ring neighbours on a multi-axis mesh
    (ignored by the fallback).
    """
    acc_name = jnp.dtype(accum_dtype).name if accum_dtype is not None \
        else jnp.dtype(x.dtype).name
    return _fused(x, w, axis_name, int(axis_size), acc_name, kernel,
                  None if mesh_axes is None else tuple(mesh_axes))


# --------------------------------------------------------------------------
# Pallas transposed-Cannon (the 2-D token-mix promotion)
# --------------------------------------------------------------------------

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
    bm = min(block_m, _ru(m, sublane(w.dtype)))
    bk = min(block_k, _ru(t, 128))
    bc = min(block_c, _ru(c, 128))
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


def _ru(n: int, mult: int) -> int:
    return -(-n // mult) * mult


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


# --------------------------------------------------------------------------
# TPU fused transposed-Cannon: q rotate hops as in-kernel remote copies
# --------------------------------------------------------------------------

def cannon_footprint_bytes(ll: int, m_l: int, t_l: int, c_l: int,
                           x_dtype) -> int:
    """VMEM for the fused Cannon: both operands double-buffered (send +
    recv each) + the f32 block accumulator."""
    e = jnp.dtype(x_dtype).itemsize
    return int(4 * m_l * t_l * e + 4 * ll * t_l * c_l * e
               + ll * m_l * c_l * 4 + ll * m_l * c_l * e)


def _cannon_kernel(ij_ref, w_ref, x_ref, o_ref,
                   w_send, w_recv, x_send, x_recv, acc,
                   wss, wrs, xss, xrs, *, q: int, mesh_axes,
                   dom_axis: str, tp_axis: str):
    """Fused transposed-Cannon: grid step s multiplies the current (w, x)
    blocks into the f32 accumulator while BOTH rotate hops (w along tp,
    x along dom; perm (t, (t-1) % q), i.e. send to predecessor) fly as
    remote copies -- the rotate steps are in-kernel.  Skew happens once
    outside (operand alignment, not the hot loop).  Slot discipline as in
    the 1-D ring."""
    s = pl.program_id(0)
    if q > 1:
        w_succ, w_pred, id_t = _ring_neighbors(tp_axis, q, mesh_axes)
        x_succ, x_pred, _ = _ring_neighbors(dom_axis, q, mesh_axes)

        def hop(h, src, dst, ssem, rsem, to, ty):
            return pltpu.make_async_remote_copy(
                src_ref=src.at[h % 2], dst_ref=dst.at[h % 2],
                send_sem=ssem.at[h % 2], recv_sem=rsem.at[h % 2],
                device_id=to, device_id_type=ty)

        @pl.when(s == 0)
        def _first():
            barrier = pltpu.get_barrier_semaphore()
            for dev in (w_succ, w_pred, x_succ, x_pred):
                pltpu.semaphore_signal(barrier, 1, device_id=dev,
                                       device_id_type=id_t)
            pltpu.semaphore_wait(barrier, 4)

        @pl.when(s > 0)
        def _wait():
            hop(s - 1, w_send, w_recv, wss, wrs, w_pred, id_t).wait()
            hop(s - 1, x_send, x_recv, xss, xrs, x_pred, id_t).wait()

        cur_w = jnp.where(s == 0, w_ref[...], w_recv[(s - 1) % 2])
        cur_x = jnp.where(s == 0, x_ref[...], x_recv[(s - 1) % 2])

        @pl.when(s < q - 1)
        def _send():
            w_send[s % 2] = cur_w
            x_send[s % 2] = cur_x
            hop(s, w_send, w_recv, wss, wrs, w_pred, id_t).start()
            hop(s, x_send, x_recv, xss, xrs, x_pred, id_t).start()
    else:
        cur_w = w_ref[...]
        cur_x = x_ref[...]

    @pl.when(s == 0)
    def _zero():
        acc[...] = jnp.zeros_like(acc)
    # [m_l, t_l] x [L, t_l, c_l] -> [m_l, L, c_l]
    acc[...] += jax.lax.dot_general(
        cur_w, cur_x, (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32)

    @pl.when(s == q - 1)
    def _emit():
        o_ref[...] = acc[...].astype(o_ref.dtype)


def _cannon_fwd_tpu(w: jax.Array, x: jax.Array, *, dom_axis: str,
                    tp_axis: str, q: int, accum_dtype, mesh_axes
                    ) -> jax.Array:
    """q multiply-accumulate steps + 2(q-1) rotate hops in ONE pallas_call.
    Inputs are the already-skewed local blocks; returns [L, m_l, c_l]
    (moved from the kernel's [m_l, L, c_l] accumulator layout)."""
    ll, t_l, c_l = x.shape
    m_l = w.shape[0]
    out_dt = jnp.dtype(accum_dtype or x.dtype)
    ij = jnp.zeros((1,), jnp.int32)  # placeholder prefetch (ids via axes)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(q,),
        in_specs=[
            pl.BlockSpec((m_l, t_l), lambda s, ij: (0, 0)),
            pl.BlockSpec((ll, t_l, c_l), lambda s, ij: (0, 0, 0)),
        ],
        out_specs=pl.BlockSpec((m_l, ll, c_l), lambda s, ij: (0, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((2, m_l, t_l), w.dtype),
            pltpu.VMEM((2, m_l, t_l), w.dtype),
            pltpu.VMEM((2, ll, t_l, c_l), x.dtype),
            pltpu.VMEM((2, ll, t_l, c_l), x.dtype),
            pltpu.VMEM((m_l, ll, c_l), jnp.float32),
            pltpu.SemaphoreType.DMA((2,)),
            pltpu.SemaphoreType.DMA((2,)),
            pltpu.SemaphoreType.DMA((2,)),
            pltpu.SemaphoreType.DMA((2,)),
        ],
    )
    out = pl.pallas_call(
        functools.partial(_cannon_kernel, q=q, mesh_axes=mesh_axes,
                          dom_axis=dom_axis, tp_axis=tp_axis),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((m_l, ll, c_l), out_dt),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",), collective_id=2),
    )(ij, w, x)
    return jnp.moveaxis(out, 0, 1)


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


@functools.partial(jax.custom_vjp, nondiff_argnums=(2, 3, 4, 5, 6))
def _fused_cannon(wl, xl, dom_axis, tp_axis, q, acc_name, mesh_axes):
    acc_dt = jnp.dtype(acc_name)
    lead = xl.shape[:-2]
    ll = math.prod(lead) if lead else 1
    if q > 1 and cannon_path(ll, wl.shape[0], wl.shape[1], xl.shape[-1],
                             xl.dtype, mesh_axes) == "tpu":
        y = _cannon_fwd_tpu(wl, xl.reshape((ll,) + xl.shape[-2:]),
                            dom_axis=dom_axis, tp_axis=tp_axis, q=q,
                            accum_dtype=acc_dt, mesh_axes=mesh_axes)
        return y.reshape(lead + y.shape[-2:])
    return cannon_t_loop(wl, xl, dom_axis=dom_axis, tp_axis=tp_axis,
                         q=q, accum_dtype=acc_dt)


def _fused_cannon_fwd(wl, xl, dom_axis, tp_axis, q, acc_name, mesh_axes):
    return (_fused_cannon(wl, xl, dom_axis, tp_axis, q, acc_name,
                          mesh_axes), (wl, xl))


def _fused_cannon_bwd(dom_axis, tp_axis, q, acc_name, mesh_axes, res, dy):
    # Backward of the fused q-hop kernel = backward of the step loop (same
    # math; the rotations transpose to reverse rotations via ppermute).
    wl, xl = res
    acc_dt = jnp.dtype(acc_name)
    _, vjp = jax.vjp(
        lambda w_, x_: cannon_t_loop(w_, x_, dom_axis=dom_axis,
                                     tp_axis=tp_axis, q=q,
                                     accum_dtype=acc_dt), wl, xl)
    return vjp(dy)


_fused_cannon.defvjp(_fused_cannon_fwd, _fused_cannon_bwd)


def fused_cannon_t(wl: jax.Array, xl: jax.Array, *, dom_axis: str,
                   tp_axis: str, q: int, accum_dtype=jnp.float32,
                   mesh_axes: Optional[Sequence[str]] = None) -> jax.Array:
    """Transposed-Cannon on the Pallas engine (already-skewed operands).

    On TPU within the VMEM budget the q multiply-accumulate steps AND the
    2(q-1) rotate hops run as ONE pallas_call (in-kernel remote copies);
    elsewhere one fused multiply-accumulate pallas_call per step with
    ppermute rotates.  Returns [..., m_l, c_l] in ``accum_dtype``.
    """
    acc_name = jnp.dtype(accum_dtype or xl.dtype).name
    return _fused_cannon(wl, xl, dom_axis, tp_axis, int(q), acc_name,
                         None if mesh_axes is None else tuple(mesh_axes))


def cannon_path(ll: int, m_l: int, t_l: int, c_l: int, x_dtype,
                mesh_axes: Optional[Sequence[str]],
                backend: Optional[str] = None,
                budget: Optional[int] = None) -> str:
    """``"tpu"`` when the fused q-hop Cannon kernel can run, else
    ``"step"`` (one pallas_call per Cannon step, rotates via ppermute)."""
    backend = backend or jax.default_backend()
    budget = VMEM_BUDGET_BYTES if budget is None else budget
    if backend != "tpu" or pltpu is None or mesh_axes is None:
        path = "step"
    elif cannon_footprint_bytes(ll, m_l, t_l, c_l, x_dtype) > budget:
        _warn_once(("cannon_vmem", ll, m_l, t_l, c_l),
                   "fused_ring: fused Cannon blocks exceed the VMEM "
                   "budget; using the per-step kernel with ppermute "
                   "rotates")
        path = "step"
    else:
        path = "tpu"
    get_tracer().counter(f"fused_ring.cannon.{path}")
    return path
