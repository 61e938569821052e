"""Precision policy: which dtype each stage of the hot path runs in.

The paper's peak-PFLOP numbers (§6) assume the MXU runs at its bf16 rate
and that the Jigsaw ring hops move half-width partial sums.  A ``Policy``
names the three dtypes that decide both:

  param_dtype    storage dtype of the trainable parameters -- the buffers
                 the train step donates and the checkpoint shards hold;
  compute_dtype  dtype of every GEMM operand AND of every byte that rides
                 a collective (ring/Cannon ``ppermute`` chunks,
                 ``psum_scatter`` inputs).  bf16 halves per-hop ICI bytes
                 relative to fp32 -- asserted on compiled HLO by the
                 ``precision_bf16`` dist scenario;
  accum_dtype    dtype partial sums are ACCUMULATED in across ranks/chunks
                 (the ring's adds, Cannon's q-step accumulator).  The MXU
                 itself always accumulates fp32 inside the Pallas kernel
                 (``preferred_element_type`` / f32 VMEM scratch); this
                 knob governs what happens BETWEEN kernel calls.

plus the optimizer split:

  master_weights fp32 master copy of every parameter lives in the Adam
                 state; the update is computed fp32-from-masters and cast
                 down into the (donated) ``param_dtype`` buffers.  Without
                 masters, repeated cast-down of tiny updates stalls
                 training once ``lr * delta`` drops below one bf16 ulp of
                 the weight.
  moment_dtype   Adam mu/nu storage.

Named presets (``get_policy``):

  fp32       everything float32 -- the numerical reference.
  bf16       mixed precision: bf16 params/compute, fp32 ring accumulation,
             fp32 master weights + fp32 moments.  This is the production
             policy: ~2x MXU throughput and ~0.5x collective bytes at
             fp32-equivalent convergence (loss-parity asserted by the
             ``precision_bf16`` scenario).
  bf16_pure  memory-minimal: bf16 everywhere incl. ring accumulation and
             moments, no masters (the "jamba-398b fits a single pod only
             with bf16 moments" regime -- accepts the convergence risk).

``policy_of(cfg)`` resolves a ModelConfig: an explicit ``cfg.precision``
names a preset; otherwise a legacy policy is derived from the config's
``param_dtype``/``compute_dtype`` fields (fp32 accumulation, no masters)
so pre-policy behavior is reproduced exactly.
"""
from __future__ import annotations

import dataclasses
from typing import Any, Optional, Union

import jax.numpy as jnp


@dataclasses.dataclass(frozen=True)
class Policy:
    name: str = "fp32"
    param_dtype: Any = jnp.float32
    compute_dtype: Any = jnp.float32
    accum_dtype: Any = jnp.float32
    master_weights: bool = False
    moment_dtype: Optional[Any] = None   # None -> param dtype

    def replace(self, **kw) -> "Policy":
        return dataclasses.replace(self, **kw)


PRESETS = {
    "fp32": Policy("fp32", jnp.float32, jnp.float32, jnp.float32,
                   master_weights=False, moment_dtype=jnp.float32),
    "bf16": Policy("bf16", jnp.bfloat16, jnp.bfloat16, jnp.float32,
                   master_weights=True, moment_dtype=jnp.float32),
    "bf16_pure": Policy("bf16_pure", jnp.bfloat16, jnp.bfloat16,
                        jnp.bfloat16, master_weights=False,
                        moment_dtype=jnp.bfloat16),
}


def get_policy(p: Union[str, Policy, None]) -> Policy:
    """Resolve a preset name (or pass a Policy through; None -> fp32)."""
    if p is None:
        return PRESETS["fp32"]
    if isinstance(p, Policy):
        return p
    if p not in PRESETS:
        raise ValueError(f"unknown precision preset {p!r} "
                         f"(have {sorted(PRESETS)})")
    return PRESETS[p]


def policy_of(cfg) -> Policy:
    """Policy for a ModelConfig.

    ``cfg.precision`` (set by ``apply_policy`` / the ``--precision``
    flag) names a preset.  When unset (None), derive the legacy policy
    from the config's dtype strings: fp32 accumulation, no master
    weights -- byte-for-byte the pre-policy behavior, so every existing
    config / test is unaffected.
    """
    name = getattr(cfg, "precision", None)
    if name:
        return get_policy(name)
    return Policy(name="legacy",
                  param_dtype=jnp.dtype(cfg.param_dtype),
                  compute_dtype=jnp.dtype(cfg.compute_dtype),
                  accum_dtype=jnp.float32, master_weights=False,
                  moment_dtype=None)


def apply_policy(cfg, p: Union[str, Policy]):
    """Return ``cfg`` with the policy threaded into its dtype fields.

    Models init params from ``cfg.param_dtype`` and the engine derives
    its JigsawConfig/AdamConfig from ``policy_of(cfg)``, so this one
    replace() is the single point where a preset takes effect."""
    pol = get_policy(p)
    return cfg.replace(precision=pol.name,
                       param_dtype=jnp.dtype(pol.param_dtype).name,
                       compute_dtype=jnp.dtype(pol.compute_dtype).name)
