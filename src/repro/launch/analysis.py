"""Compiled-artifact analysis: roofline terms from the dry-run.

Sources (compiled for a TPU v5e -- no wall clock involved):
  * ``compiled.cost_analysis()``  -> HLO FLOPs + bytes accessed (per-device
    program, post-SPMD-partitioning).
  * ``compiled.as_text()``        -> optimized HLO; we sum operand bytes of
    every all-gather / all-reduce / reduce-scatter / all-to-all /
    collective-permute.  Collectives inside while-loop bodies (lax.scan
    over layers) are multiplied by the loop trip count, which we recover
    from the HLO constant the induction variable is compared against.

Roofline terms (seconds), per device:
  compute    = flops / PEAK_FLOPS
  memory     = bytes_accessed / HBM_BW
  collective = collective_bytes / ICI_BW
"""
from __future__ import annotations

import dataclasses
import re
from typing import Dict, List, Optional, Tuple

import numpy as np


@dataclasses.dataclass(frozen=True)
class Peaks:
    """Published per-chip peaks of one TPU kind."""
    flops_bf16: float         # FLOP/s
    hbm_bw: float             # bytes/s


# Per-chip peaks keyed by jax ``Device.device_kind``.  Source: Google Cloud
# documentation, "TPU v5e": 197 TFLOP/s bf16, 819 GB/s HBM per chip.
PEAKS: Dict[str, Peaks] = {
    "TPU v5 lite": Peaks(flops_bf16=197e12, hbm_bw=819e9),
}
V5E = PEAKS["TPU v5 lite"]
# the analytic Fig. 7 model, the dry-run and the roofline terms below are
# stated for one v5e chip
PEAK_FLOPS_BF16 = V5E.flops_bf16
HBM_BW = V5E.hbm_bw
ICI_BW = 50e9                 # bytes/s per link (~usable per-chip here)


def peaks_for(device) -> Optional[Peaks]:
    """Peaks of the chip a run is on (a jax ``Device``).

    None off the TPU: a CPU has no entry, so its cost model has no
    compute roofline term.  A TPU kind missing from ``PEAKS`` raises -- an unknown chip
    never borrows another chip's peaks."""
    if device.platform != "tpu":
        return None
    try:
        return PEAKS[device.device_kind]
    except KeyError:
        raise ValueError(
            f"no published peaks for TPU kind {device.device_kind!r}; add "
            f"them to launch/analysis.PEAKS") from None

_DTYPE_BYTES = {
    "pred": 1, "s8": 1, "u8": 1, "s16": 2, "u16": 2, "bf16": 2, "f16": 2,
    "s32": 4, "u32": 4, "f32": 4, "s64": 8, "u64": 8, "f64": 8, "c64": 8,
    "c128": 16,
}

_COLLECTIVES = ("all-gather", "all-reduce", "reduce-scatter", "all-to-all",
                "collective-permute")

_SHAPE_RE = re.compile(r"(\w+)\[([\d,]*)\]")


def _shape_bytes(type_str: str) -> int:
    """bytes of an HLO type string like 'bf16[4,128]' or a tuple
    '(bf16[2], f32[3,3])'."""
    total = 0
    for m in _SHAPE_RE.finditer(type_str):
        dt, dims = m.groups()
        if dt not in _DTYPE_BYTES:
            continue
        n = 1
        for d in dims.split(","):
            if d:
                n *= int(d)
        total += n * _DTYPE_BYTES[dt]
    return total


@dataclasses.dataclass
class CollectiveStats:
    counts: Dict[str, int]
    bytes_by_kind: Dict[str, float]

    @property
    def total_bytes(self) -> float:
        return float(sum(self.bytes_by_kind.values()))


def _split_computations(hlo: str) -> Dict[str, List[str]]:
    """Split an HLO module's text into its computations.

    Handles both the post-optimization header form
    ``%name (params) -> type {`` and the pre-optimization short form
    ``name {`` (``compiler_ir(dialect='hlo')`` -- which the
    ``precision_bf16`` dist scenario parses, because backend
    legalization may rewrite collective dtypes: CPU widens bf16
    collectives to f32)."""
    comps: Dict[str, List[str]] = {}
    cur = None
    for line in hlo.splitlines():
        stripped = line.strip()
        if (stripped.endswith("{") and not stripped.startswith("ROOT")
                and "=" not in stripped.split("(")[0]
                and not stripped.startswith("HloModule")):
            m = re.match(r"(?:ENTRY\s+)?%?([\w\.\-]+)\s*(?:\([^)]*\))?"
                         r"\s*(?:->.*)?{$", stripped)
            if m:
                cur = m.group(1)
                comps[cur] = []
                continue
        if cur is not None:
            comps[cur].append(line)
        if line.startswith("}") or stripped == "}":
            cur = None
    return comps


def _find_trip_counts(hlo: str) -> Dict[str, int]:
    """Map while-body computation name -> trip count.

    XLA canonicalizes counted loops; we recover the count from the
    ``trip_count`` backend hint if present, else from the constant bound
    in the condition computation referenced by each while op.
    """
    trips: Dict[str, int] = {}
    # while ops: ... while(...), condition=%cond_name, body=%body_name
    for m in re.finditer(
            r"while\([^)]*\)[^\n]*condition=%?([\w\.\-]+)[^\n]*body=%?"
            r"([\w\.\-]+)", hlo):
        cond, body = m.groups()
        # find the condition computation and its comparison constant
        cm = re.search(
            re.escape(cond) + r"[^{]*{(.*?)\n}", hlo, re.S)
        count = None
        if cm:
            consts = re.findall(r"constant\((\d+)\)", cm.group(1))
            if consts:
                count = max(int(c) for c in consts)
        trips[body] = count if count else 1
    return trips


def collective_stats(hlo: str) -> CollectiveStats:
    comps = _split_computations(hlo)
    trips = _find_trip_counts(hlo)
    counts = {k: 0 for k in _COLLECTIVES}
    bts = {k: 0.0 for k in _COLLECTIVES}
    for comp_name, lines in comps.items():
        mult = 1
        # nested loops: multiply by every enclosing trip count whose body
        # matches; (single level is the common case for our scans)
        for body, t in trips.items():
            if comp_name == body or comp_name.startswith(body):
                mult = t
                break
        for line in lines:
            for kind in _COLLECTIVES:
                # match ' = TYPE kind(' and avoid -start/-done duplicates
                m = re.search(r"=\s+([^\s]+)\s+" + kind + r"(?:-start)?\(",
                              line)
                if m:
                    counts[kind] += mult
                    bts[kind] += mult * _shape_bytes(m.group(1))
                    break
    return CollectiveStats(counts, bts)


@dataclasses.dataclass
class Roofline:
    flops: float                  # per device
    bytes_accessed: float         # per device
    collective_bytes: float       # per device
    n_devices: int
    compute_s: float
    memory_s: float
    collective_s: float
    bottleneck: str
    model_flops: Optional[float] = None
    useful_ratio: Optional[float] = None

    def as_dict(self):
        return dataclasses.asdict(self)


def roofline_from(compiled, *, n_devices: int,
                  model_flops_total: Optional[float] = None,
                  peak=PEAK_FLOPS_BF16, hbm=HBM_BW, ici=ICI_BW) -> Roofline:
    ca = compiled.cost_analysis()
    flops = float(ca.get("flops", 0.0))
    bytes_acc = float(sum(v for k, v in ca.items()
                          if k.startswith("bytes accessed")
                          and "{" not in k.replace("{}", "")) or
                      ca.get("bytes accessed", 0.0))
    # 'bytes accessed' plain key is the total; operand-indexed keys are
    # the breakdown. Prefer the plain key when present.
    if "bytes accessed" in ca:
        bytes_acc = float(ca["bytes accessed"])
    stats = collective_stats(compiled.as_text())
    comp_s = flops / peak
    mem_s = bytes_acc / hbm
    coll_s = stats.total_bytes / ici
    terms = {"compute": comp_s, "memory": mem_s, "collective": coll_s}
    bottleneck = max(terms, key=terms.get)
    mf = model_flops_total / n_devices if model_flops_total else None
    return Roofline(
        flops=flops, bytes_accessed=bytes_acc,
        collective_bytes=stats.total_bytes, n_devices=n_devices,
        compute_s=comp_s, memory_s=mem_s, collective_s=coll_s,
        bottleneck=bottleneck, model_flops=mf,
        useful_ratio=(mf / flops if (mf and flops) else None))


# ---------------------------------------------------------------------------
# Analytic FLOPs / HBM-bytes model
#
# XLA's cost_analysis() does NOT account for while-loop bodies (verified:
# flops are constant in n_layers under lax.scan), so the dry-run derives
# compute/memory roofline terms analytically from the exact matmul dims --
# we wrote the model code, so the dims are known precisely -- and uses the
# compiled HLO only for the collective schedule (trip counts recovered
# from the loop conditions) and the memory_analysis() fit proof.
# ---------------------------------------------------------------------------

def _dense_matmul_params(cfg) -> float:
    """Matmul-participating params per *layer stack* (excl. embeddings),
    counting each expert (for per-token math use active fraction)."""
    D = cfg.d_model
    hd = cfg.d_head
    attn = (D * cfg.n_heads * hd + 2 * D * cfg.n_kv_heads * hd
            + cfg.n_heads * hd * D) if cfg.n_heads else 0
    ffn = (3 if cfg.ffn_kind == "swiglu" else 2) * D * cfg.d_ff
    ssm = 0
    if cfg.ssm_heads:
        din = cfg.ssm_d_inner
        dinp = 2 * din + 2 * cfg.ssm_groups * cfg.ssm_state + cfg.ssm_heads
        ssm = D * dinp + din * D
    total = 0.0
    for i in range(cfg.n_layers):
        if cfg.family == "ssm" or not cfg.is_attn_layer(i):
            total += ssm
        else:
            total += attn
        if cfg.is_moe_layer(i):
            total += cfg.top_k * ffn     # active experts only
        elif cfg.d_ff:
            total += ffn
    return total


def flops_forward(cfg, batch: int, seq: int) -> Dict[str, float]:
    """Forward-pass FLOPs by component for one global batch."""
    D = cfg.d_model
    T = batch * seq
    out = {}
    out["matmul"] = 2.0 * _dense_matmul_params(cfg) * T
    # attention score/AV matmuls (causal not exploited, matching XLA)
    if cfg.n_heads:
        attn = 0.0
        for i in range(cfg.n_layers):
            if cfg.family == "ssm" or not cfg.is_attn_layer(i):
                continue
            w = cfg.layer_window(i)
            s_eff = min(seq, w) if w is not None else seq
            attn += 4.0 * batch * cfg.n_heads * cfg.d_head * seq * s_eff
        out["attention"] = attn
    # SSD chunked scan (intra-chunk quadratic + state einsums)
    if cfg.ssm_heads:
        Q = cfg.ssm_chunk
        H, Pd, N = cfg.ssm_heads, cfg.ssm_head_dim, cfg.ssm_state
        n_ssm = sum(1 for i in range(cfg.n_layers)
                    if cfg.family == "ssm" or not cfg.is_attn_layer(i))
        per_tok = (2 * Q * H * N            # CB^T within chunk
                   + 2 * Q * H * Pd         # att @ x
                   + 6 * H * Pd * N)        # states + y_inter
        out["ssd_scan"] = n_ssm * T * per_tok
    # MoE dispatch/combine einsums
    if cfg.n_experts:
        n_moe = sum(1 for i in range(cfg.n_layers) if cfg.is_moe_layer(i))
        # dispatch [T,E,C]x[T,D] + combine: 2 einsums of 2*T*(k*cf)*D
        out["moe_dispatch"] = n_moe * 4.0 * T * cfg.top_k * cfg.capacity_factor * D
        out["router"] = n_moe * 2.0 * T * cfg.n_experts * D
    # LM head / embeddings
    if cfg.vocab_size:
        out["head"] = 2.0 * T * D * cfg.vocab_padded
    if cfg.family == "mixer":
        t_tok = (cfg.wm_lat // cfg.wm_patch) * (cfg.wm_lon // cfg.wm_patch)
        pin = cfg.wm_patch ** 2 * cfg.wm_channels
        B = batch
        out["matmul"] = 2.0 * B * (
            t_tok * pin * D * 2                                   # enc+dec
            + cfg.n_layers * (2 * t_tok * cfg.wm_d_tok * D        # token MLP
                              + 2 * t_tok * D * cfg.wm_d_ch))     # chan MLP
    return out


def flops_step(cfg, shape_kind: str, batch: int, seq: int) -> float:
    """Total FLOPs for one step of the given kind (global)."""
    f = sum(flops_forward(cfg, batch, seq).values())
    if shape_kind == "train":
        # fwd + bwd(2x) + remat re-fwd
        return f * (4.0 if cfg.remat else 3.0)
    if shape_kind == "prefill":
        return f
    # decode: one token against a cache
    fd = sum(flops_forward(cfg, batch, 1).values())
    # attention against the cache: 4*B*H*hd*S_cache per attn layer
    if cfg.n_heads:
        extra = 0.0
        for i in range(cfg.n_layers):
            if cfg.family == "ssm" or not cfg.is_attn_layer(i):
                continue
            w = cfg.layer_window(i)
            s_eff = min(seq, w) if w is not None else seq
            extra += 4.0 * batch * cfg.n_heads * cfg.d_head * s_eff
        fd += extra
    return fd


def hbm_bytes_step(cfg, shape_kind: str, batch: int, seq: int,
                   param_bytes_total: float, cache_bytes_total: float = 0.0,
                   opt_bytes_total: float = 0.0) -> float:
    """Approximate HBM traffic (global, all devices summed) for one step.

    train:   params fwd+bwd+update (3 reads + 2 writes) + opt states rw
             + activations (~14 residual-stream rw per layer, remat ~+50%)
             + attention score traffic
    prefill: params read + activations write/read once
    decode:  params read + full cache read + cache write (1 slot)
    """
    D = cfg.d_model
    T = batch * seq
    act_dtype = 2.0
    if shape_kind == "train":
        p = 3 * param_bytes_total + 2 * param_bytes_total
        p += 2 * opt_bytes_total
        act = 14.0 * cfg.n_layers * T * D * act_dtype
        if cfg.remat:
            act *= 1.5
        if cfg.n_heads:
            for i in range(cfg.n_layers):
                if cfg.family == "ssm" or not cfg.is_attn_layer(i):
                    continue
                w = cfg.layer_window(i)
                s_eff = min(seq, w) if w is not None else seq
                act += 6.0 * batch * cfg.n_heads * seq * s_eff * act_dtype
        return p + act
    if shape_kind == "prefill":
        act = 8.0 * cfg.n_layers * T * D * act_dtype
        if cfg.n_heads:
            for i in range(cfg.n_layers):
                if not cfg.is_attn_layer(i) or cfg.family == "ssm":
                    continue
                w = cfg.layer_window(i)
                s_eff = min(seq, w) if w is not None else seq
                act += 2.0 * batch * cfg.n_heads * seq * s_eff * act_dtype
        return param_bytes_total + act
    # decode
    return param_bytes_total + cache_bytes_total * 1.0 + \
        cache_bytes_total / max(seq, 1) + 8.0 * cfg.n_layers * batch * D * act_dtype


def model_flops_train(cfg, tokens: int) -> float:
    """MODEL_FLOPS = 6*N*D (dense) / 6*N_active*D (MoE) for one step."""
    n = cfg.param_count()
    if cfg.n_experts and cfg.top_k:
        # subtract inactive expert params
        d_ff_all = cfg.n_experts
        active_frac = cfg.top_k / cfg.n_experts
        # recompute: replace expert params with active fraction
        moe_layers = sum(1 for i in range(cfg.n_layers)
                         if cfg.is_moe_layer(i))
        per_layer_moe = cfg.n_experts * (3 if cfg.ffn_kind == "swiglu"
                                         else 2) * cfg.d_model * cfg.d_ff
        n = n - moe_layers * per_layer_moe * (1 - active_frac)
    return 6.0 * n * tokens


def model_flops_decode(cfg, new_tokens: int) -> float:
    """2*N_active per generated token (forward only)."""
    n = cfg.param_count()
    if cfg.n_experts and cfg.top_k:
        moe_layers = sum(1 for i in range(cfg.n_layers)
                         if cfg.is_moe_layer(i))
        per_layer_moe = cfg.n_experts * (3 if cfg.ffn_kind == "swiglu"
                                         else 2) * cfg.d_model * cfg.d_ff
        n = n - moe_layers * per_layer_moe * (1 - cfg.top_k / cfg.n_experts)
    return 2.0 * n * new_tokens
