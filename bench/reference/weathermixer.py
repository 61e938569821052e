"""Plain float32 WeatherMixer: the yardstick the benchmark's `correct` uses.

Written from the paper (Kieckhefen et al. 2025, "Jigsaw: Training
Multi-Billion-Parameter AI Weather Models with Optimized Model
Parallelism", arXiv:2507.05753), sections 3, 5 and 6:

  encoder   non-overlapping p x p patches of the [lat, lon, C] grid, each
            flattened to p*p*C values and mapped linearly to d_emb
            (the patch convolution written as a linear, section 5);
  blocks    n MLP-Mixer blocks, each a token-mixing MLP over the T patch
            tokens and a channel-mixing MLP over d_emb, LayerNorm before
            and a residual around each (section 3);
  decoder   a linear from d_emb back to p*p*C, un-patched to the grid;
  blend     out = sigmoid(lambda_c) * x + (1 - sigmoid(lambda_c)) * y, a
            learned per-variable weighted fraction of the input and the
            prediction (section 3).

Training: latitude-weighted MSE with the pressure-level weights of
section 6, gradients clipped to a global norm of 1, Adam with fp32 master
weights, linear warm-up then cosine decay of the learning rate.

Departures, each a choice the paper leaves open: GELU is the tanh
approximation; LayerNorm has eps 1e-5 and an affine scale and bias; the
token MLP keeps d_tok hidden units and biases on both linears; weights
are stored [d_out, d_in].

Every matrix product goes through one ``gemm``.  ``gemm_f32`` is float32
at ``Precision.HIGHEST``; ``gemm_fp8`` rounds both operands (and, in the
backward pass, the cotangent) to float8_e4m3 with a per-tensor scale:
the control that must fail the comparison.  Nothing here imports the
program under test.
"""
from __future__ import annotations

import math
from functools import partial
from typing import Callable, Dict, List, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

HIGHEST = jax.lax.Precision.HIGHEST
FP8_MAX = 448.0        # largest finite float8_e4m3fn


# --------------------------------------------------------------------------
# matrix products
# --------------------------------------------------------------------------

def gemm_f32(x: jax.Array, w: jax.Array) -> jax.Array:
    """x [..., k] times w [n, k] -> [..., n], float32, highest precision."""
    return jnp.einsum("...k,nk->...n", x.astype(jnp.float32),
                      w.astype(jnp.float32), precision=HIGHEST)


def _q8(a: jax.Array) -> jax.Array:
    """Round to float8_e4m3 with one scale for the whole tensor."""
    a = a.astype(jnp.float32)
    s = jnp.maximum(jnp.max(jnp.abs(a)), 1e-30) / FP8_MAX
    return (a / s).astype(jnp.float8_e4m3fn).astype(jnp.float32) * s


@jax.custom_vjp
def gemm_fp8(x: jax.Array, w: jax.Array) -> jax.Array:
    return gemm_f32(_q8(x), _q8(w))


def _fp8_fwd(x, w):
    return gemm_fp8(x, w), (x, w)


def _fp8_bwd(res, g):
    x, w = res
    gq, xq, wq = _q8(g), _q8(x), _q8(w)
    dx = jnp.einsum("...n,nk->...k", gq, wq, precision=HIGHEST)
    dw = jnp.einsum("...n,...k->nk", gq, xq, precision=HIGHEST)
    return dx.astype(x.dtype), dw.astype(w.dtype)


gemm_fp8.defvjp(_fp8_fwd, _fp8_bwd)

GEMMS = {"f32": gemm_f32, "fp8": gemm_fp8}


# --------------------------------------------------------------------------
# the model
# --------------------------------------------------------------------------

def gelu(x):
    c = math.sqrt(2.0 / math.pi)
    return 0.5 * x * (1.0 + jnp.tanh(c * (x + 0.044715 * x ** 3)))


def layernorm(x, scale, bias, eps: float = 1e-5):
    mu = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(x - mu), axis=-1, keepdims=True)
    return (x - mu) / jnp.sqrt(var + eps) * scale + bias


def patchify(x, p: int):
    """[lat, lon, C] -> [T, p*p*C]; tokens row-major over (lat, lon)."""
    lat, lon, c = x.shape
    x = x.reshape(lat // p, p, lon // p, p, c).transpose(0, 2, 1, 3, 4)
    return x.reshape((lat // p) * (lon // p), p * p * c)


def unpatchify(y, lat: int, lon: int, p: int, c: int):
    y = y.reshape(lat // p, lon // p, p, p, c).transpose(0, 2, 1, 3, 4)
    return y.reshape(lat, lon, c)


def block(bp: Dict, h, gemm: Callable):
    """One mixing block on one sample's tokens h [T, d]."""
    u = layernorm(h, bp["tok_norm"]["scale"], bp["tok_norm"]["bias"])
    ut = u.T                                                   # [d, T]
    v = gelu(gemm(ut, bp["tok_fc1"]["w"]) + bp["tok_fc1"]["b"])
    h = h + (gemm(v, bp["tok_fc2"]["w"]) + bp["tok_fc2"]["b"]).T
    u = layernorm(h, bp["ch_norm"]["scale"], bp["ch_norm"]["bias"])
    v = gelu(gemm(u, bp["ch_fc1"]["w"]) + bp["ch_fc1"]["b"])
    return h + gemm(v, bp["ch_fc2"]["w"]) + bp["ch_fc2"]["b"]


def forward(params: Dict, x, cfg: Dict, gemm: Callable = gemm_f32,
            remat: bool = False):
    """One sample: x [lat, lon, C] -> the state one step (6 h) later."""
    p, lat, lon, c = cfg["patch"], cfg["lat"], cfg["lon"], cfg["channels"]
    x = x.astype(jnp.float32)
    h = gemm(patchify(x, p), params["encoder"]["w"]) + params["encoder"]["b"]
    body = partial(block, gemm=gemm)
    if remat:
        body = jax.checkpoint(body)
    for i in range(cfg["n_layers"]):
        bp = jax.tree.map(lambda a: a[i], params["blocks"])
        h = body(bp, h)
    y = gemm(h, params["decoder"]["w"]) + params["decoder"]["b"]
    y = unpatchify(y, lat, lon, p, c)
    lam = jax.nn.sigmoid(params["blend"])
    return lam * x + (1.0 - lam) * y


# --------------------------------------------------------------------------
# loss
# --------------------------------------------------------------------------

def latitude_weights(lat: int) -> np.ndarray:
    """cos(latitude), rows from +90 to -90 degrees, mean 1."""
    w = np.maximum(np.cos(np.deg2rad(np.linspace(90.0, -90.0, lat))), 0.0)
    return (w / w.mean()).astype(np.float32)


def channel_weights(channels: int, n_surface: int = 4, n_vars: int = 5,
                    n_levels: int = 13) -> np.ndarray:
    """Surface variables weigh 1; each of the 5 pressure-level variables
    weighs its 13 levels, high to low pressure, as section 6 gives."""
    lvl = np.array([1, 1, 1, 1, 1, 1, .9, .8, .7, .6, .5, .4, .3])
    w = np.ones(channels)
    if channels >= n_surface + n_vars * n_levels:
        for v in range(n_vars):
            lo = n_surface + v * n_levels
            w[lo:lo + n_levels] = lvl
    return w.astype(np.float32)


def sample_loss(params, x, y, cfg: Dict, gemm: Callable = gemm_f32,
                remat: bool = False):
    pred = forward(params, x, cfg, gemm, remat)
    err = jnp.square(pred - y.astype(jnp.float32))
    err = err * latitude_weights(cfg["lat"])[:, None, None]
    err = err * channel_weights(cfg["channels"])[None, None, :]
    return jnp.mean(err)


# --------------------------------------------------------------------------
# training: gradient, clip, Adam with fp32 masters
# --------------------------------------------------------------------------

def warmup_cosine(step: int, *, base_lr: float, init_lr: float,
                  min_lr: float, warmup_steps: int, total_steps: int) -> float:
    """Linear warm-up from init_lr to base_lr, then cosine to min_lr."""
    if step < warmup_steps:
        return init_lr + (base_lr - init_lr) * step / max(warmup_steps, 1)
    t = min(max((step - warmup_steps) / max(total_steps - warmup_steps, 1),
                0.0), 1.0)
    return min_lr + 0.5 * (base_lr - min_lr) * (1.0 + math.cos(math.pi * t))


def _tree_sq(t) -> List[jax.Array]:
    return [jnp.sum(jnp.square(a)) for a in jax.tree.leaves(t)]


_JITTED: Dict[Tuple, Callable] = {}


def _jitted(kind: str, cfg: Dict, gemm: Callable) -> Callable:
    """One compiled program per (kind, configuration, gemm)."""
    key = (kind, tuple(sorted(cfg.items())), gemm)
    fn = _JITTED.get(key)
    if fn is None:
        if kind == "grad":
            vg = jax.value_and_grad(
                partial(sample_loss, cfg=cfg, gemm=gemm, remat=True))

            def accumulate(total, params, x, y, w):
                loss, g = vg(params, x, y)
                return loss, jax.tree.map(lambda t, gi: t + w * gi, total, g)
            # the running sum is donated: one sample's gradient is added in
            # place, so the batch never holds two sums at once
            fn = jax.jit(accumulate, donate_argnums=0)
        else:
            fn = jax.jit(partial(forward, cfg=cfg, gemm=gemm))
        _JITTED[key] = fn
    return fn


def batch_grad(params, xs: Sequence, ys: Sequence, cfg: Dict,
               gemm: Callable = gemm_f32, rows: slice = slice(None)):
    """Mean loss and mean gradient over a batch, one sample at a time
    (blocks of one row keep the activations of a full-grid sample in
    memory, not the batch's).  ``rows`` selects the rows that count: the
    planted fault "half of the batch left out" passes half of them."""
    grad_fn = _jitted("grad", cfg, gemm)
    idx = list(range(len(xs)))[rows]
    w = jnp.float32(1.0 / len(idx))
    loss = 0.0
    grads = jax.tree.map(lambda a: jnp.zeros(a.shape, jnp.float32), params)
    for i in idx:
        l, grads = grad_fn(grads, params, xs[i], ys[i], w)
        loss = loss + float(l)
    return loss / len(idx), grads


@partial(jax.jit, static_argnames=("b1", "b2", "eps", "clip"),
         donate_argnums=(0, 1, 2))
def adam_step(master, mu, nu, grads, lr, step, *, b1: float = 0.9,
              b2: float = 0.95, eps: float = 1e-8, clip: float = 1.0):
    """One Adam update on fp32 masters, after a global-norm clip.  Returns
    the new state and the norm of each leaf of the clipped gradient."""
    gnorm = jnp.sqrt(sum(_tree_sq(grads)))
    scale = jnp.minimum(1.0, clip / jnp.maximum(gnorm, 1e-12))
    grads = jax.tree.map(lambda g: g * scale, grads)
    mu = jax.tree.map(lambda m, g: b1 * m + (1 - b1) * g, mu, grads)
    nu = jax.tree.map(lambda v, g: b2 * v + (1 - b2) * g * g, nu, grads)
    c1, c2 = 1.0 - b1 ** step, 1.0 - b2 ** step
    master = jax.tree.map(
        lambda p, m, v: p - lr * (m / c1) / (jnp.sqrt(v / c2) + eps),
        master, mu, nu)
    norms = jax.tree.map(lambda g: jnp.sqrt(jnp.sum(jnp.square(g))), grads)
    return master, mu, nu, norms


@jax.jit
def _gap_norm(a, b):
    return jnp.sqrt(jnp.sum(jnp.square(a.astype(jnp.float32)
                                       - b.astype(jnp.float32))))


def _keyed(tree) -> Dict[str, float]:
    return {jax.tree_util.keystr(p): float(v)
            for p, v in jax.tree_util.tree_leaves_with_path(tree)}


def train_readings(params0, batches: Sequence[Tuple[Sequence, Sequence]],
                   cfg: Dict, lr_at: Callable[[int], float],
                   gemm: Callable = gemm_f32,
                   rows: slice = slice(None)) -> Dict:
    """Follow the first len(batches) updates from params0.

    Returns the loss of every step, the norm of every leaf of the first
    (clipped) gradient, and the norm of every leaf's change over all the
    steps.  ``params0`` may be host arrays: the device then holds only the
    masters, the moments and one gradient sum."""
    with jax.default_matmul_precision("highest"):
        master = jax.tree.map(lambda a: jnp.array(a, jnp.float32, copy=True),
                              params0)
        mu = jax.tree.map(jnp.zeros_like, master)
        nu = jax.tree.map(jnp.zeros_like, master)
        losses, grad_norms = [], None
        for s, (xs, ys) in enumerate(batches):
            loss, grads = batch_grad(master, xs, ys, cfg, gemm, rows)
            losses.append(float(loss))
            master, mu, nu, norms = adam_step(
                master, mu, nu, grads, jnp.float32(lr_at(s)),
                jnp.float32(s + 1))
            del grads
            if s == 0:
                grad_norms = _keyed(norms)
        del mu, nu
        change = _keyed(jax.tree.map(_gap_norm, master, params0))
    return {"losses": losses, "grad_norms": grad_norms,
            "change_norms": change}
