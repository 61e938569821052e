"""Operations and bytes of WeatherMixer, from its shapes alone.

A configuration is the dict of its JSON file (``n_layers``, ``d_emb``,
``d_tok``, ``d_ch``, ``lat``, ``lon``, ``channels``, ``patch``).  A GEMM
is (rows, out, contract): ``rows x contract`` times ``contract x out``.
"""
from __future__ import annotations

from typing import Dict, List, Tuple

Gemm = Tuple[str, int, int, int]


def n_tokens(cfg: Dict) -> int:
    return (cfg["lat"] // cfg["patch"]) * (cfg["lon"] // cfg["patch"])


def patch_dim(cfg: Dict) -> int:
    return cfg["patch"] * cfg["patch"] * cfg["channels"]


def forward_gemms(cfg: Dict) -> List[Gemm]:
    """The GEMMs of one sample's forward pass, in order."""
    t, pd, d = n_tokens(cfg), patch_dim(cfg), cfg["d_emb"]
    out = [("encoder", t, d, pd)]
    for _ in range(cfg["n_layers"]):
        out += [("tok_fc1", d, cfg["d_tok"], t),
                ("tok_fc2", d, t, cfg["d_tok"]),
                ("ch_fc1", t, cfg["d_ch"], d),
                ("ch_fc2", t, d, cfg["d_ch"])]
    out.append(("decoder", t, pd, d))
    return out


def gemm_flops(g: Gemm) -> float:
    _, m, n, k = g
    return 2.0 * m * n * k


def gemm_bytes(g: Gemm, itemsize: int = 2) -> float:
    """HBM bytes of a GEMM at its least: each operand read once and the
    output written once, in bf16."""
    _, m, n, k = g
    return float(itemsize * (m * k + k * n + m * n))


def train_gemms(cfg: Dict) -> List[Gemm]:
    """The GEMMs of one sample's update: the forward; with ``remat`` the
    mixing blocks' forward once more in the backward; and for each GEMM
    the gradients of its weight and of its input, except the encoder's
    input, which needs none."""
    fwd = forward_gemms(cfg)
    out = list(fwd)
    if cfg["remat"]:
        out += [g for g in fwd if g[0] not in ("encoder", "decoder")]
    for name, m, n, k in fwd:
        out.append((name + ".dw", n, k, m))
        if name != "encoder":
            out.append((name + ".dx", m, k, n))
    return out


def forward_flops(cfg: Dict) -> float:
    """Model FLOPs of one sample's forward pass."""
    return sum(gemm_flops(g) for g in forward_gemms(cfg))


def train_flops(cfg: Dict) -> float:
    """Model FLOPs of one sample's update: forward plus backward, the
    backward twice the forward.  Recomputed work does not count."""
    return 3.0 * forward_flops(cfg)


def param_count(cfg: Dict) -> int:
    t, pd, d = n_tokens(cfg), patch_dim(cfg), cfg["d_emb"]
    block = (2 * t * cfg["d_tok"] + cfg["d_tok"] + t
             + 2 * d * cfg["d_ch"] + cfg["d_ch"] + d + 4 * d)
    return (pd * d + d) + cfg["n_layers"] * block + (d * pd + pd) \
        + cfg["channels"]


def least_time(flops: float, nbytes: float, peak: Dict[str, float]
               ) -> float:
    """Roofline: the larger of compute time and HBM time at the peaks."""
    return max(flops / peak["bf16_flops_per_s"],
               nbytes / peak["hbm_bytes_per_s"])
