"""Weights of a configuration, made on the device from the seed.

The layout is the one the paper's equations name (``reference/``) and the
program's engines take as ``init_params``: weights ``[d_out, d_in]``,
biases, LayerNorm scale and bias, the per-variable blend, and the mixing
blocks stacked on a leading axis of ``n_layers``.  Linear weights are
LeCun-normal, biases and the blend zero, LayerNorm scales one.  They are
rounded once to the configuration's parameter type, so the program and
the reference start from the same numbers.
"""
from __future__ import annotations

import math
from functools import partial
from typing import Dict

import jax
import jax.numpy as jnp

from harness import flops
from harness.synth import key_for

PARAM_DTYPES = {"bf16": jnp.bfloat16, "fp32": jnp.float32}


def shapes(cfg: Dict) -> Dict:
    """Leaf shapes of the layout, as a nested dict of tuples."""
    t, pd, d = flops.n_tokens(cfg), flops.patch_dim(cfg), cfg["d_emb"]
    n, dt, dc = cfg["n_layers"], cfg["d_tok"], cfg["d_ch"]

    def lin(d_in, d_out, lead=()):
        return {"w": lead + (d_out, d_in), "b": lead + (d_out,)}

    norm = {"scale": (n, d), "bias": (n, d)}
    return {
        "encoder": lin(pd, d),
        "blocks": {"tok_norm": norm, "tok_fc1": lin(t, dt, (n,)),
                   "tok_fc2": lin(dt, t, (n,)), "ch_norm": dict(norm),
                   "ch_fc1": lin(d, dc, (n,)), "ch_fc2": lin(dc, d, (n,))},
        "decoder": lin(d, pd),
        "blend": (cfg["channels"],),
    }


def _is_shape(x) -> bool:
    return isinstance(x, tuple)


@partial(jax.jit, static_argnames=("cfg_items", "dtype"))
def _make(key, cfg_items, dtype):
    cfg = dict(cfg_items)
    tree = shapes(cfg)
    leaves, treedef = jax.tree.flatten(tree, is_leaf=_is_shape)
    paths = [jax.tree_util.keystr(p) for p, _ in
             jax.tree_util.tree_flatten_with_path(tree, is_leaf=_is_shape)[0]]
    keys = jax.random.split(key, len(leaves))
    out = []
    for k, path, shape in zip(keys, paths, leaves):
        if path.endswith("['w']"):
            fan_in = shape[-1]
            w = jax.random.normal(k, shape, jnp.float32) / math.sqrt(fan_in)
            out.append(w.astype(dtype))
        elif path.endswith("['scale']"):
            out.append(jnp.ones(shape, jnp.float32))
        elif path.endswith("['blend']"):
            out.append(jnp.zeros(shape, jnp.float32))
        else:                                   # biases, norm shifts
            out.append(jnp.zeros(shape, dtype if path.endswith("['b']")
                                 else jnp.float32))
    return jax.tree.unflatten(treedef, out)


def make(seed: int, cfg: Dict, dtype=None):
    """The weights of ``cfg`` for ``seed``, in one jitted call."""
    dtype = dtype or PARAM_DTYPES[cfg["precision"]]
    return _make(key_for(seed, 1), tuple(sorted(cfg_sizes(cfg).items())),
                 dtype)


def cfg_sizes(cfg: Dict) -> Dict:
    """The size keys of a configuration (hashable, for jit)."""
    keys = ("n_layers", "d_emb", "d_tok", "d_ch", "lat", "lon", "channels",
            "patch")
    return {k: cfg[k] for k in keys}


def as_f32(params):
    return jax.tree.map(lambda a: jnp.asarray(a, jnp.float32), params)
