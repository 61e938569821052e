"""Analytic FLOP / wire-byte counts of a training step (DESIGN.md §14).

``build_cost_model`` prices one step once per (ModelConfig, Jigsaw
scheme, mesh shape): global FLOPs, the per-hop wire bytes of the
explicit ring schedule (``core.jigsaw.comm_schedule_jigsaw_1d`` -- the
same schedule the fused kernel enforces), and the compute and
collective roofline terms they imply.  These are counts, not
measurements: the engine stamps them into the run's meta header
(``as_meta``) and no step record is derived from them.  The FLOPs side
reuses ``launch/analysis.py``'s exact matmul-dims model; the roofline
terms are the same formulas as ``benchmarks/fig7_roofline.py``
(``fig7_point`` below reproduces that benchmark's rows bit-for-bit,
pinned by tests/test_telemetry.py).

``hlo_collective_bytes`` cross-checks the analytic wire model against a
compiled step's actual HLO collectives (``launch/analysis.py`` parse).
"""
from __future__ import annotations

import dataclasses
import math
from typing import Any, Dict, Optional

import numpy as np

from repro.core.jigsaw import (comm_schedule_jigsaw_1d,
                               comm_volume_jigsaw_1d,
                               comm_volume_jigsaw_2d)
from repro.launch import analysis as A

# fig7's I/O model constants (paper §5: one 0.25-deg f32 sample over a
# shared Lustre-like host stream)
DISK_BW = 2e9
SAMPLE_BYTES = 4 * 721 * 1440 * 69


def _wire_dtype_bytes(cfg) -> int:
    """Bytes per element on the Jigsaw wire: the policy's compute dtype
    (what the ring ships -- DESIGN.md §10), param dtype otherwise."""
    from repro.core import precision
    pol = precision.policy_of(cfg)
    dt = pol.compute_dtype if pol.name != "legacy" else None
    dt = dt or getattr(cfg, "param_dtype", None) or "float32"
    return np.dtype(dt).itemsize


def _tokens_per_sample(cfg) -> int:
    if cfg.family == "mixer":
        return (cfg.wm_lat // cfg.wm_patch) * (cfg.wm_lon // cfg.wm_patch)
    return 0


@dataclasses.dataclass(frozen=True)
class StepCostModel:
    """Analytic per-step costs for one (config, scheme, mesh) triple.

    ``flops_per_step`` / ``comm_bytes_per_device`` are for ONE rollout
    step (rollout=1)."""
    arch: str
    scheme: str
    impl: str
    n_model: int
    n_data: int
    batch: int
    flops_per_step: float          # global fwd+bwd(+remat) FLOPs
    comm_bytes_per_device: float   # jigsaw collective bytes, per device
    hops: int                      # ring hops per jigsaw'd linear fwd
    bytes_per_hop: float           # wire bytes per hop per device
    wire_dtype_bytes: int
    approx_comm: bool              # True = non-mixer fallback estimate
    device: str = "TPU v5 lite"    # device_kind the peaks belong to
    peak_flops: Optional[float] = A.PEAK_FLOPS_BF16   # None: no peak
    ici_bw: float = A.ICI_BW

    @property
    def n_devices(self) -> int:
        return max(self.n_model * self.n_data, 1)

    @property
    def t_compute_s(self) -> Optional[float]:
        """Compute roofline term: per-device FLOPs at peak (None on a
        device with no published peak)."""
        if self.peak_flops is None:
            return None
        return self.flops_per_step / self.n_devices / self.peak_flops

    @property
    def t_collective_s(self) -> float:
        """Collective roofline term: per-device wire bytes at ICI BW."""
        return self.comm_bytes_per_device / self.ici_bw

    def as_meta(self) -> Dict[str, Any]:
        """JSON-serializable constants for the trace JSONL header."""
        d = dataclasses.asdict(self)
        d["t_compute_s"] = self.t_compute_s
        d["t_collective_s"] = self.t_collective_s
        d["n_devices"] = self.n_devices
        return d


def build_cost_model(cfg, *, n_model: int = 1, n_data: int = 1,
                     batch: int = 1, seq_len: int = 128, device=None,
                     ici: float = A.ICI_BW) -> StepCostModel:
    """Cost model for one training step of ``cfg`` on an
    (n_model x n_data) mesh with global batch ``batch``.

    Peaks: ``device`` (the jax Device the run is on) picks its row of
    ``launch/analysis.PEAKS`` -- none for a CPU, an error for a TPU kind
    the table lacks.  ``device=None`` is the analytic v5e model.

    FLOPs: ``launch/analysis.flops_step(kind="train")`` (fwd + bwd, remat
    re-forward when configured) -- exact matmul dims.

    Wire bytes: the Jigsaw collective volume of every sharded linear.
    For the mixer family this is the paper's Fig. 7 model -- fwd+bwd
    (3x) of 2 ring reduce-scatters of ``[tokens, d_ch]`` per layer under
    scheme="1d" (``comm_volume_jigsaw_1d``), Cannon block rotates under
    scheme="2d" (``comm_volume_jigsaw_2d``) -- at the policy's wire
    dtype.  Non-mixer families get a d_model-proportional estimate
    (flagged ``approx_comm``)."""
    n_model = max(int(n_model), 1)
    n_data = max(int(n_data), 1)
    if device is None:
        kind, peaks = "TPU v5 lite", A.V5E
    else:
        kind, peaks = device.device_kind, A.peaks_for(device)
    flops = A.flops_step(cfg, "train", batch, seq_len)
    wire = _wire_dtype_bytes(cfg)
    scheme = cfg.scheme if n_model > 1 else "none"
    impl = getattr(cfg, "impl", "ring") or "ring"

    comm = 0.0
    hops, hop_bytes, approx = 0, 0.0, False
    if scheme != "none" and n_model > 1:
        if cfg.family == "mixer":
            tokens = batch * _tokens_per_sample(cfg)
            m = cfg.wm_d_ch
        else:
            tokens = batch * seq_len
            m = cfg.d_model
            approx = True
        q = int(math.isqrt(n_model))
        if scheme == "2d" and q * q == n_model and q > 1:
            vol = comm_volume_jigsaw_2d(tokens, m, q, dtype_bytes=wire)
            comm = 3.0 * vol.bytes_per_device * 2 * cfg.n_layers
            hops = 2 * (q - 1)
            hop_bytes = vol.bytes_per_device / hops
        else:
            p = n_model
            sched = comm_schedule_jigsaw_1d(
                tokens, m, cfg.d_model // p or 1, p,
                dtype_bytes=wire,
                impl=impl if impl in ("ring", "ring_chunked",
                                      "ring_fused") else "ring")
            comm = 3.0 * (comm_volume_jigsaw_1d(tokens, m, p,
                                                dtype_bytes=wire)
                          .bytes_per_device * 2 * cfg.n_layers)
            hops, hop_bytes = sched.hops, sched.bytes_per_hop
    return StepCostModel(
        arch=cfg.arch_id, scheme=scheme, impl=impl,
        n_model=n_model, n_data=n_data, batch=batch,
        flops_per_step=float(flops), comm_bytes_per_device=float(comm),
        hops=hops, bytes_per_hop=float(hop_bytes),
        wire_dtype_bytes=wire, approx_comm=approx, device=kind,
        peak_flops=None if peaks is None else peaks.flops_bf16, ici_bw=ici)


# ---------------------------------------------------------------------------
# fig7 parity + HLO cross-check
# ---------------------------------------------------------------------------

def fig7_point(cfg, way: int, impl: Optional[str] = None
               ) -> Dict[str, float]:
    """One row of the Fig. 7 roofline, exactly as
    ``benchmarks/fig7_roofline.py`` computes it (same formulas, same
    constants) -- the pinned reference for the roofline terms.

    Returns t_step_s / tflops_per_dev / peak_frac / regime for a mixer
    config at jigsaw width ``way`` (1, 2 = 1-D ring, 4 = 2-D Cannon);
    ``impl`` in ("ring_chunked", "ring_fused") applies the overlap
    schedule ``t_comp/p + max(t_comp (p-1)/p, t_coll)``."""
    flops = 3 * sum(A.flops_forward(cfg, 1, 0).values())
    t_tokens = _tokens_per_sample(cfg)
    t_io = SAMPLE_BYTES / (way * DISK_BW)
    t_comp = flops / (way * A.PEAK_FLOPS_BF16)
    if way == 1:
        t_coll, p_ring = 0.0, 1
    elif way == 2:
        v = 3 * (comm_volume_jigsaw_1d(t_tokens, cfg.wm_d_ch, way)
                 .bytes_per_device * 2 * cfg.n_layers)
        t_coll, p_ring = v / A.ICI_BW, way
    else:
        v = 3 * (comm_volume_jigsaw_2d(t_tokens, cfg.wm_d_ch, 2)
                 .bytes_per_device * 2 * cfg.n_layers)
        t_coll, p_ring = v / A.ICI_BW, 2
    if impl in ("ring_chunked", "ring_fused") and p_ring > 1:
        t_cc = t_comp / p_ring + max(t_comp * (p_ring - 1) / p_ring,
                                     t_coll)
    else:
        t_cc = t_comp + t_coll
    t_step = max(t_io, t_cc)
    achieved = flops / t_step / way
    return {"t_step_s": t_step, "t_io_s": t_io, "t_comp_s": t_comp,
            "t_coll_s": t_coll,
            "tflops_per_dev": achieved / 1e12,
            "peak_frac": achieved / A.PEAK_FLOPS_BF16,
            "regime": "io" if t_io > t_cc else "compute-comm"}


def hlo_collective_bytes(compiled) -> float:
    """Total collective bytes of a compiled step (per device), from the
    HLO text -- the measured side of the wire-byte cross-check."""
    return A.collective_stats(compiled.as_text()).total_bytes
