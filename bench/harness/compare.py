"""How ``correct`` is decided: the program's readings against the plain
float32 reference, each gap beside the cell's limit.

Training (per the cell's first three updates):
  loss_gap     |loss - ref| / |ref| of the first step;
  grad_gap     over the leaves, the largest |norm - ref norm| of the first
               gradient as Adam received it, over the larger of the
               leaf's reference norm and the median leaf's;
  change_gap   the same for each leaf's change over the three updates.
A leaf whose reference gradient is under a thousandth of the median
leaf's is left out of ``change_gap``: Adam moves it by round-off alone.

Serving: for a sample of the delivered forecasts, drawn from the seed
with the longest leads in it, ``forecast_gap`` is the largest
||served - ref|| / ||ref - initial state|| over the sample: the error
against what the reference rollout changed.
"""
from __future__ import annotations

import os
import statistics
import sys
from typing import Dict, Sequence, Tuple

import numpy as np

Check = Tuple[str, float, float]


def _ref():
    here = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    if here not in sys.path:
        sys.path.insert(0, here)
    from reference import weathermixer
    return weathermixer


def train_gaps(program: Dict, ref: Dict) -> Dict:
    """Every training gap, and the leaves left out of ``change_gap``."""
    loss = [abs(p - r) / abs(r) for p, r in
            zip(program["losses"], ref["losses"])]
    g_ref = ref["grad_norms"]
    med_g = statistics.median(g_ref.values())

    def worst(prog: Dict[str, float], refn: Dict[str, float],
              keys: Sequence[str]) -> float:
        med = statistics.median(refn[k] for k in keys)
        return max(abs(prog[k] - refn[k]) / max(refn[k], med, 1e-30)
                   for k in keys)

    keys = sorted(g_ref)
    moved = [k for k in keys if g_ref[k] >= 1e-3 * med_g]
    return {"loss_gap": loss[0], "loss_gap_later": max(loss[1:]),
            "left_out": [k for k in keys if k not in moved],
            "grad_gap": worst(program["grad_norms"], g_ref, keys),
            "change_gap": worst(program["change_norms"],
                                ref["change_norms"], moved)}


def reference_train(res: Dict, seed: int, pool, gemm: str = "f32",
                    rows: slice = slice(None)) -> Dict:
    """The reference's readings over the cell's first updates."""
    import jax
    from harness import train, weights
    wm = _ref()
    cfg, lr = res["config"], res["cell"]["lr"]
    # the starting weights wait on the host: the device holds the steps
    params0 = jax.device_get(weights.make(seed, cfg))
    batches = [(pool[s % len(pool)]["fields"], pool[s % len(pool)]["target"])
               for s in range(train.CHECK_STEPS)]
    sched = {k: lr[k] for k in ("base_lr", "init_lr", "min_lr",
                                "warmup_steps", "total_steps")}
    return wm.train_readings(
        params0, batches, weights.cfg_sizes(cfg),
        lambda s: wm.warmup_cosine(s, **sched), wm.GEMMS[gemm], rows)


def train_checks(program: Dict, res: Dict, seed: int, pool):
    """The compared gaps beside their limits, and every gap: the cell's
    ``limits`` name the gaps that are compared."""
    ref = reference_train(res, seed, pool)
    gaps = train_gaps(program, ref)
    lim = res["cell"]["limits"]
    return [(k, gaps[k], lim[k]) for k in sorted(lim)], gaps


def forecast_gap(served: np.ndarray, ref: np.ndarray,
                 initial: np.ndarray) -> float:
    served, ref, initial = (np.asarray(a, np.float64)
                            for a in (served, ref, initial))
    return float(np.linalg.norm(served - ref)
                 / max(np.linalg.norm(ref - initial), 1e-30))


def reference_rollouts(res: Dict, seed: int, states: Sequence[np.ndarray],
                       wanted: Dict[int, set], gemm: str = "f32"
                       ) -> Dict[int, Dict[int, np.ndarray]]:
    """For each pool state index and the leads wanted from it, the
    reference's forecast at each of those leads (host arrays)."""
    import jax
    import jax.numpy as jnp
    from harness import weights
    wm = _ref()
    cfg = res["config"]
    sizes = weights.cfg_sizes(cfg)
    params = weights.as_f32(weights.make(seed, cfg))
    fwd = wm._jitted("forward", sizes, wm.GEMMS[gemm])
    out: Dict[int, Dict[int, np.ndarray]] = {}
    with jax.default_matmul_precision("highest"):
        for idx, leads in sorted(wanted.items()):
            x = jnp.asarray(states[idx])
            out[idx] = {}
            for step in range(1, max(leads) + 1):
                x = fwd(params, x)
                if step in leads:
                    out[idx][step] = np.asarray(x)
    return out
