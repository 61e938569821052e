"""Composable Jigsaw modules: functional param-init / apply pairs.

Everything in this framework is a pure function over parameter pytrees
(nested dicts of jax.Arrays).  ``JigsawConfig`` selects how each linear
layer completes its distributed contraction:

  scheme="1d", impl in jigsaw.Impl1D            (paper 2-way, n-way)
  scheme="2d"                                   (paper 4-way, Cannon)

``impl="rs"`` (psum_scatter) is the default production path;
``"ring_chunked"`` is the paper's own schedule (one output-chunk GEMM
issued before each hop so send overlaps compute); ``"ring"`` is the
monolithic-GEMM approximation of it; ``"gspmd"`` lets XLA derive the
collectives from sharding constraints alone (beyond-paper comparison);
``"allreduce"`` is the Megatron-style psum-then-slice baseline.

``kernel`` selects the compute engine of every local GEMM: ``"xla"``
(dot_general) or ``"pallas"`` (the MXU-tiled blocked kernel,
kernels/block_matmul.py -- f32 VMEM accumulation, and where the
contraction is already complete, i.e. the undistributed scheme="none"
path, the bias add and GELU ride the kernel's fused epilogue).  Under a
distributed scheme the epilogue cannot fuse: the partial products are
incomplete until the reduce-scatter / ring finishes, so bias/activation
apply after the collective (DESIGN.md §8).
"""
from __future__ import annotations

import dataclasses
import math
import warnings
from typing import Optional

import jax
import jax.numpy as jnp

from repro.core import jigsaw
from repro.core.sharding import RULES_1D, ShardingRules, constrain


@dataclasses.dataclass(frozen=True)
class JigsawConfig:
    rules: ShardingRules = RULES_1D
    scheme: str = "1d"            # "1d" | "2d" | "none"
    impl: str = "rs"              # for scheme="1d"
    accum_dtype: Optional[jnp.dtype] = jnp.float32
    fsdp: bool = False            # weights also sharded over data (huge archs)
    kernel: str = "xla"           # "xla" | "pallas" (local GEMM engine)
    # precision-policy compute dtype (core/precision): every linear casts
    # its operands here before the GEMM/collectives, so bf16 halves both
    # MXU time and per-hop ring bytes.  None = no cast (legacy).
    compute_dtype: Optional[jnp.dtype] = None

    def __post_init__(self):
        # Fail fast on unknown knobs and surface combinations that would
        # otherwise be *silently* ignored (the scheme dispatch only reads
        # ``impl`` under scheme="1d").
        if self.scheme not in ("1d", "2d", "none"):
            raise ValueError(f"JigsawConfig: unknown scheme {self.scheme!r}"
                             " (expected '1d' | '2d' | 'none')")
        if self.impl not in jigsaw.Impl1D:
            raise ValueError(f"JigsawConfig: unknown impl {self.impl!r} "
                             f"(expected one of {jigsaw.Impl1D})")
        if self.kernel not in jigsaw.Kernels:
            raise ValueError(f"JigsawConfig: unknown kernel {self.kernel!r}"
                             f" (expected one of {jigsaw.Kernels})")
        if self.scheme != "1d" and self.impl != "rs":
            warnings.warn(
                f"JigsawConfig: impl={self.impl!r} only applies to "
                f"scheme='1d'; scheme={self.scheme!r} ignores it "
                "(2-D uses Cannon, 'none' is undistributed)",
                stacklevel=3)

    def replace(self, **kw) -> "JigsawConfig":
        return dataclasses.replace(self, **kw)


DEFAULT_JIGSAW = JigsawConfig()
GSPMD_JIGSAW = JigsawConfig(impl="gspmd")


def head_config(cfg: JigsawConfig) -> JigsawConfig:
    """Jigsaw config for the LM head / unembed.

    The explicit reduce-scatter is the paper's scheme for *inner* layers,
    but for the final vocab projection its transpose (an all-gather of the
    full-vocab gradient, ~22 GiB/device at train_4k) is catastrophic.
    With sharding constraints only, the cross-entropy stays element-wise
    over the vocab-sharded logits and the gradient never materializes
    unsharded (EXPERIMENTS.md #Perf, iteration 1)."""
    if cfg.scheme == "1d":
        return cfg.replace(impl="gspmd")
    return cfg


# ---------------------------------------------------------------------------
# Linear
# ---------------------------------------------------------------------------

def linear_init(key: jax.Array, d_in: int, d_out: int, *,
                dtype=jnp.float32, bias: bool = True, scale: float = None):
    """Weights stored [d_out, d_in] (y = x @ w.T + b), LeCun-normal init."""
    scale = (1.0 / math.sqrt(d_in)) if scale is None else scale
    w = jax.random.normal(key, (d_out, d_in), dtype=jnp.float32) * scale
    p = {"w": w.astype(dtype)}
    if bias:
        p["b"] = jnp.zeros((d_out,), dtype)
    return p


def linear_apply(params, x: jax.Array, cfg: JigsawConfig = DEFAULT_JIGSAW,
                 *, domain_dim: int = -2,
                 epilogue: str = "none") -> jax.Array:
    """``y = epilogue(x @ w.T + b)``.

    ``epilogue`` ("none" | "gelu" | "silu") only fuses into the GEMM on
    the undistributed pallas path, where the contraction is complete
    inside the kernel; distributed schemes apply it after the collective.
    """
    w = params["w"]
    b = params.get("b")
    act = None if epilogue == "none" else getattr(jax.nn, epilogue)
    if cfg.scheme == "2d":
        y = jigsaw.jigsaw_linear_2d(x, w, b, rules=cfg.rules,
                                    domain_dim=domain_dim,
                                    accum_dtype=cfg.accum_dtype,
                                    kernel=cfg.kernel,
                                    compute_dtype=cfg.compute_dtype)
        return y if act is None else act(y)
    if cfg.scheme == "1d":
        y = jigsaw.jigsaw_linear(x, w, b, rules=cfg.rules, impl=cfg.impl,
                                 accum_dtype=cfg.accum_dtype,
                                 w_data_sharded=cfg.fsdp,
                                 kernel=cfg.kernel,
                                 compute_dtype=cfg.compute_dtype)
        return y if act is None else act(y)
    # scheme="none": plain local matmul (single-device / inside-shard_map)
    x, w, b = jigsaw._cast_operands(x, w, b, cfg.compute_dtype)
    if cfg.kernel == "pallas":
        # contraction completes in-kernel: bias + activation ride the
        # fused epilogue, the activation never round-trips to HBM.
        from repro.kernels import ops
        return ops.matmul_nd(x, w, b, epilogue=epilogue)
    y = jax.lax.dot_general(
        x, w, (((x.ndim - 1,), (1,)), ((), ())),
        preferred_element_type=cfg.accum_dtype or x.dtype).astype(x.dtype)
    y = y if b is None else y + b
    return y if act is None else act(y)


# ---------------------------------------------------------------------------
# MLP (two linears + GELU) -- the WeatherMixer building block
# ---------------------------------------------------------------------------

def mlp_init(key: jax.Array, d_in: int, d_hidden: int, d_out: int, *,
             dtype=jnp.float32, bias: bool = True):
    k1, k2 = jax.random.split(key)
    return {"fc1": linear_init(k1, d_in, d_hidden, dtype=dtype, bias=bias),
            "fc2": linear_init(k2, d_hidden, d_out, dtype=dtype, bias=bias)}


def mlp_apply(params, x: jax.Array, cfg: JigsawConfig = DEFAULT_JIGSAW,
              *, activation=jax.nn.gelu, domain_dim: int = -2) -> jax.Array:
    if cfg.kernel == "pallas" and cfg.scheme == "none" \
            and activation is jax.nn.gelu:
        # Fused two-GEMM path (the WeatherMixer mixing MLPs and every
        # gelu-kind encoder/decoder FFN): the first GEMM's bias + GELU
        # run in its VMEM epilogue, the hidden activation feeds the
        # second GEMM without an unfused elementwise pass between.
        from repro.kernels import ops
        x, w1, b1 = jigsaw._cast_operands(
            x, params["fc1"]["w"], params["fc1"].get("b"), cfg.compute_dtype)
        _, w2, b2 = jigsaw._cast_operands(
            x, params["fc2"]["w"], params["fc2"].get("b"), cfg.compute_dtype)
        return ops.mixer_mlp(x, w1, b1, w2, b2)
    h = linear_apply(params["fc1"], x, cfg, domain_dim=domain_dim)
    h = activation(h)
    return linear_apply(params["fc2"], h, cfg, domain_dim=domain_dim)


def param_spec_tree(params, rules: ShardingRules, scheme: str = "1d"):
    """PartitionSpecs for a linear/MLP param subtree (w: jigsaw layout,
    b: sharded along the tp axis to match the output)."""
    from jax.sharding import PartitionSpec as P

    def spec_for(path, leaf):
        name = path[-1]
        if name == "w":
            return rules.weight(leaf.ndim) if scheme != "none" \
                else rules.replicated(leaf.ndim)
        if name == "b":
            return P(rules.tp_axis) if scheme != "none" else P(None)
        return rules.replicated(leaf.ndim)

    def walk(tree, path=()):
        if isinstance(tree, dict):
            return {k: walk(v, path + (k,)) for k, v in tree.items()}
        return spec_for(path, tree)

    return walk(params)
