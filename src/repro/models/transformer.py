"""Generic decoder-only transformer LM.

Covers the dense / MoE / VLM assigned architectures:
  dbrx-132b, internlm2-1.8b, pixtral-12b, gemma3-27b, phi3.5-moe-42b,
  stablelm-3b, h2o-danube-1.8b.

Layers are *stacked* (params carry a leading [L] dim, built by vmapping the
per-layer init) and executed with ``lax.scan`` so the lowered HLO is O(one
layer) regardless of depth -- essential for the 40-pair multi-pod dry-run.
Per-layer heterogeneity (gemma3's 5 local : 1 global attention pattern) is
expressed as a traced per-layer window parameter, so the scan body stays
homogeneous.

All projections are Jigsaw linears (repro.core), so the paper's parallelism
is the default execution mode of every architecture.
"""
from __future__ import annotations

from functools import partial
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import ModelConfig
from repro.core.api import DEFAULT_JIGSAW, JigsawConfig
from repro.core.sharding import constrain
from repro.models import layers as L

# sentinel: no sliding window (a NumPy scalar: importing this module must
# not start a JAX backend -- a supervising parent would claim the chip)
FULL_WINDOW = np.int32(2 ** 30)


def _norm_init(cfg: ModelConfig, d: int):
    return (L.layernorm_init(d) if cfg.norm == "layernorm"
            else L.rmsnorm_init(d))


def _norm_apply(cfg: ModelConfig, p, x):
    return (L.layernorm_apply(p, x) if cfg.norm == "layernorm"
            else L.rmsnorm_apply(p, x))


def layer_init(key: jax.Array, cfg: ModelConfig):
    """One decoder layer's params (no leading dim)."""
    dtype = jnp.dtype(cfg.param_dtype)
    ka, kf = jax.random.split(key)
    p = {
        "attn_norm": _norm_init(cfg, cfg.d_model),
        "attn": L.attention_init(ka, cfg.d_model, cfg.n_heads,
                                 cfg.n_kv_heads, cfg.d_head, dtype=dtype,
                                 bias=cfg.attn_bias),
        "ffn_norm": _norm_init(cfg, cfg.d_model),
    }
    if cfg.qk_norm:
        p["qk_norm"] = {"q": L.rmsnorm_init(cfg.d_head),
                        "k": L.rmsnorm_init(cfg.d_head)}
    if cfg.is_moe_layer(0):   # uniform-MoE archs (dbrx, phi3.5)
        p["moe"] = L.moe_init(kf, cfg.d_model, cfg.d_ff, cfg.n_experts,
                              kind=cfg.ffn_kind, dtype=dtype)
    else:
        p["ffn"] = L.ffn_init(kf, cfg.d_model, cfg.d_ff, kind=cfg.ffn_kind,
                              dtype=dtype)
    return p


def init(key: jax.Array, cfg: ModelConfig):
    dtype = jnp.dtype(cfg.param_dtype)
    ke, kl, ku = jax.random.split(key, 3)
    layer_keys = jax.random.split(kl, cfg.n_layers)
    params = {
        "embed": L.embed_init(ke, cfg.vocab_padded, cfg.d_model, dtype=dtype),
        "layers": jax.vmap(partial(layer_init, cfg=cfg))(layer_keys),
        "final_norm": _norm_init(cfg, cfg.d_model),
    }
    if not cfg.tie_embeddings:
        params["lm_head"] = L.linear_init(ku, cfg.d_model, cfg.vocab_padded,
                                          dtype=dtype, bias=False)
    return params


def layer_windows(cfg: ModelConfig) -> jnp.ndarray:
    """Per-layer attention window (traced into the scan body)."""
    ws = [cfg.layer_window(i) for i in range(cfg.n_layers)]
    return jnp.array([w if w is not None else 2 ** 30 for w in ws],
                     jnp.int32)


def _kv_spec(cfg: ModelConfig, jcfg: JigsawConfig):
    """Layer-local cache spec [B, S, Hkv, hd] mirroring specs.cache_specs."""
    from jax.sharding import PartitionSpec as P
    import jax as _jax
    rules = jcfg.rules
    mesh = _jax.sharding.get_abstract_mesh()
    p = mesh.shape.get(rules.tp_axis, 1)
    if p == 1:
        return None
    mode = cfg.kv_shard
    if mode == "auto":
        mode = "heads" if cfg.n_kv_heads % p == 0 else "seq"
    ba = tuple(a for a in rules.batch_axes if a in mesh.shape) or None
    if mode == "heads":
        return P(ba, None, rules.tp_axis, None)
    if mode == "headdim":
        return P(ba, None, None, rules.tp_axis)
    return P(ba, rules.tp_axis, None, None)


def _layer_apply(lp, x, *, cfg: ModelConfig, jcfg: JigsawConfig,
                 positions, window, kv_cache=None, rolling=False,
                 collect_kv=False, aux_in=0.0):
    """One decoder layer. window: traced scalar (2**30 = full causal)."""
    h = _norm_apply(cfg, lp["attn_norm"], x)
    # Traced windows require the mask form (dq - dk < window); sdpa takes
    # window as an array transparently.
    attn_out, new_cache = L.attention_apply(
        lp["attn"], h, n_heads=cfg.n_heads, n_kv_heads=cfg.n_kv_heads,
        d_head=cfg.d_head, positions=positions, cfg=jcfg,
        causal=True, window=window, rope_theta=cfg.rope_theta,
        soft_cap=cfg.attn_soft_cap, kv_cache=kv_cache, rolling=rolling,
        collect_kv=collect_kv,
        kv_spec=_kv_spec(cfg, jcfg) if kv_cache is not None else None,
        qk_norm=lp.get("qk_norm"), q_chunk=cfg.attn_q_chunk)
    x = x + attn_out
    h = _norm_apply(cfg, lp["ffn_norm"], x)
    if "moe" in lp:
        # decode: tokens-in-flight is tiny; never drop (capacity >= T)
        cf = cfg.capacity_factor if kv_cache is None else float(cfg.n_experts)
        ffn_out, aux = L.moe_apply(lp["moe"], h, top_k=cfg.top_k,
                                   capacity_factor=cf, cfg=jcfg)
        aux_in = aux_in + aux
    else:
        ffn_out = L.ffn_apply(lp["ffn"], h, jcfg)
    x = x + ffn_out
    x = constrain(x, jcfg.rules.act(x.ndim))
    return x, new_cache, aux_in


def apply(params, batch, cfg: ModelConfig,
          jcfg: JigsawConfig = DEFAULT_JIGSAW) -> Tuple[jax.Array, jax.Array]:
    """Training / prefill forward pass.

    batch: {"tokens": [B, S]} (+ "embeds": [B, P, D] for VLM prefix).
    Returns (logits [B, S_total, vocab_padded], moe_aux_loss scalar).
    """
    tokens = batch["tokens"]
    x = L.embed_apply(params["embed"], tokens)
    if batch.get("embeds") is not None:
        # VLM: vision-frontend stub embeddings are prepended to the text.
        x = jnp.concatenate([batch["embeds"].astype(x.dtype), x], axis=1)
    b, s, _ = x.shape
    positions = jnp.arange(s)          # 1-D: keeps attention masks [S, S]
    x = constrain(x, jcfg.rules.act(x.ndim))
    windows = layer_windows(cfg)

    def body(carry, xs):
        h, aux = carry
        lp, w = xs
        h, _, aux = _layer_apply(lp, h, cfg=cfg, jcfg=jcfg,
                                 positions=positions, window=w, aux_in=aux)
        return (h, aux), None

    body_fn = jax.checkpoint(body) if cfg.remat else body
    (x, aux), _ = jax.lax.scan(body_fn, (x, jnp.float32(0.0)),
                               (params["layers"], windows))
    x = _norm_apply(cfg, params["final_norm"], x)
    if cfg.tie_embeddings:
        logits = L.unembed_apply(params["embed"], x, jcfg)
    else:
        from repro.core.api import head_config
        logits = L.linear_apply(params["lm_head"], x, head_config(jcfg))
    return logits, aux


# ---------------------------------------------------------------------------
# Serving (prefill handled by ``apply``; decode below)
# ---------------------------------------------------------------------------

def _period(cfg: ModelConfig) -> int:
    """Length of the repeating layer pattern (1 for uniform archs)."""
    return cfg.local_global_ratio + 1 if cfg.local_global_ratio > 0 else 1


def init_cache(cfg: ModelConfig, batch_size: int, max_len: int,
               dtype=jnp.bfloat16):
    """KV cache pytree.

    Uniform archs: {"pos", "k", "v"} with k/v [L, B, S, Hkv, hd]; if ALL
    layers share a sliding window, S = min(window, max_len) (rolling) --
    this is what makes long_500k feasible for h2o-danube.

    local:global archs (gemma3): the layer stack is viewed as
    ``n_periods`` repeats of (ratio local + 1 global); local layers get
    window-sized rolling buffers [n_periods, ratio, B, w, ...], global
    layers full-length ones [n_periods, 1, B, S, ...].  Leftover layers
    (depth % period) get their own buffers.  Decode scans over periods so
    layer ORDER is preserved exactly.
    """
    kvshape = lambda nl, s: (nl, batch_size, s, cfg.n_kv_heads, cfg.d_head)
    per = _period(cfg)
    if per == 1:
        w = cfg.sliding_window
        s = min(max_len, w) if w is not None else max_len
        return {"pos": jnp.zeros((batch_size,), jnp.int32),
                "k": jnp.zeros(kvshape(cfg.n_layers, s), dtype),
                "v": jnp.zeros(kvshape(cfg.n_layers, s), dtype)}
    n_per, leftover = divmod(cfg.n_layers, per)
    w = min(cfg.local_window or max_len, max_len)
    ratio = cfg.local_global_ratio
    cache = {
        "pos": jnp.zeros((batch_size,), jnp.int32),
        "lk": jnp.zeros((n_per, ratio) + kvshape(0, w)[1:], dtype),
        "lv": jnp.zeros((n_per, ratio) + kvshape(0, w)[1:], dtype),
        "gk": jnp.zeros((n_per,) + kvshape(0, max_len)[1:], dtype),
        "gv": jnp.zeros((n_per,) + kvshape(0, max_len)[1:], dtype),
    }
    if leftover:  # trailing local layers (gemma3: 62 = 10*6 + 2)
        cache["rk"] = jnp.zeros(kvshape(leftover, w), dtype)
        cache["rv"] = jnp.zeros(kvshape(leftover, w), dtype)
    return cache


def prefill_cache(params, batch, cfg: ModelConfig, jcfg: JigsawConfig,
                  max_len: int, dtype=jnp.bfloat16):
    """Fused prefill: ONE teacher-forced forward over the whole prompt,
    capturing every layer's post-RoPE K/V from the scan and writing them
    back into a fresh decode cache -- O(1) applies instead of O(S)
    decode steps (the ISSUE-8 replacement for the token-wise prefill
    loop, which serve/step.py keeps as the parity reference).

    Returns (logits [B, S, V], cache) positioned exactly as if the
    prompt had been fed token-by-token through ``decode_step``: token p
    lands at slot ``p % s_max`` -- the same rolling slots the token-wise
    writes use -- so decode reads it back with identical absolute-
    position bookkeeping.

    Uniform layer stacks only (``_period == 1``, including all-sliding-
    window rolling caches); local:global stacks (gemma3) raise
    NotImplementedError and the caller falls back token-wise.
    """
    if _period(cfg) != 1:
        raise NotImplementedError("fused prefill: uniform layer stacks "
                                  "only (local:global falls back)")
    if batch.get("embeds") is not None:
        raise NotImplementedError("fused prefill: text prompts only")
    tokens = batch["tokens"]
    b, s = tokens.shape
    x = L.embed_apply(params["embed"], tokens)
    positions = jnp.arange(s)
    x = constrain(x, jcfg.rules.act(x.ndim))
    windows = layer_windows(cfg)

    def body(carry, xs):
        h, aux = carry
        lp, w = xs
        h, kv, aux = _layer_apply(lp, h, cfg=cfg, jcfg=jcfg,
                                  positions=positions, window=w,
                                  collect_kv=True, aux_in=aux)
        return (h, aux), (kv["k"], kv["v"])

    body_fn = jax.checkpoint(body) if cfg.remat else body
    (x, _aux), (ks, vs) = jax.lax.scan(body_fn, (x, jnp.float32(0.0)),
                                       (params["layers"], windows))
    x = _norm_apply(cfg, params["final_norm"], x)
    if cfg.tie_embeddings:
        logits = L.unembed_apply(params["embed"], x, jcfg)
    else:
        from repro.core.api import head_config
        logits = L.linear_apply(params["lm_head"], x, head_config(jcfg))

    cache = init_cache(cfg, b, max_len, dtype)
    s_max = cache["k"].shape[2]
    if cfg.sliding_window is None and s > s_max:
        raise ValueError(f"prompt length {s} > cache max_len {s_max}")
    m = min(s, s_max)   # a rolling cache keeps only the last window
    slots = np.arange(s - m, s) % s_max
    ck = cache["k"].at[:, :, slots].set(ks[:, :, s - m:].astype(dtype))
    cv = cache["v"].at[:, :, slots].set(vs[:, :, s - m:].astype(dtype))
    return logits, {"pos": jnp.full((b,), s, jnp.int32), "k": ck, "v": cv}


def decode_step(params, cache, tokens, cfg: ModelConfig,
                jcfg: JigsawConfig = DEFAULT_JIGSAW):
    """One decode step. tokens: [B, 1]. Returns (logits [B, 1, V], cache)."""
    x = L.embed_apply(params["embed"], tokens)
    pos = cache["pos"]
    positions = pos[:, None]
    windows = layer_windows(cfg)
    per = _period(cfg)

    def run_layer(lp, h, w, kc, vc, rolling):
        kv_cache = {"k": kc, "v": vc, "pos": pos}
        h, nc, _ = _layer_apply(lp, h, cfg=cfg, jcfg=jcfg,
                                positions=positions, window=w,
                                kv_cache=kv_cache, rolling=rolling)
        return h, nc["k"], nc["v"]

    if per == 1:
        def body(h, xs):
            lp, w, kc, vc = xs
            h, nk, nv = run_layer(lp, h, w, kc, vc,
                                  rolling=cfg.sliding_window is not None)
            return h, (nk, nv)

        x, (nk, nv) = jax.lax.scan(
            body, x, (params["layers"], windows, cache["k"], cache["v"]))
        new_cache = {"pos": pos + 1, "k": nk, "v": nv}
    else:
        n_per, leftover = divmod(cfg.n_layers, per)
        ratio = cfg.local_global_ratio
        main = jax.tree.map(
            lambda a: a[:n_per * per].reshape((n_per, per) + a.shape[1:]),
            params["layers"])
        w_local = jnp.int32(cfg.local_window)

        def body(h, xs):
            lp, lk, lv, gk, gv = xs
            nlk, nlv = [], []
            for j in range(per):
                lpj = jax.tree.map(lambda a: a[j], lp)
                if j < ratio:   # local layer
                    h, k2, v2 = run_layer(lpj, h, w_local, lk[j], lv[j],
                                          rolling=True)
                    nlk.append(k2); nlv.append(v2)
                else:           # global layer
                    h, gk, gv = run_layer(lpj, h, FULL_WINDOW, gk, gv,
                                          rolling=False)
            return h, (jnp.stack(nlk), jnp.stack(nlv), gk, gv)

        x, (lk, lv, gk, gv) = jax.lax.scan(
            body, x, (main, cache["lk"], cache["lv"], cache["gk"],
                      cache["gv"]))
        new_cache = {"pos": pos + 1, "lk": lk, "lv": lv, "gk": gk, "gv": gv}
        if leftover:
            rest = jax.tree.map(lambda a: a[n_per * per:], params["layers"])

            def body_r(h, xs):
                lp, kc, vc = xs
                h, nk, nv = run_layer(lp, h, w_local, kc, vc, rolling=True)
                return h, (nk, nv)

            x, (rk, rv) = jax.lax.scan(body_r, x,
                                       (rest, cache["rk"], cache["rv"]))
            new_cache["rk"], new_cache["rv"] = rk, rv

    x = _norm_apply(cfg, params["final_norm"], x)
    if cfg.tie_embeddings:
        logits = L.unembed_apply(params["embed"], x, jcfg)
    else:
        from repro.core.api import head_config
        logits = L.linear_apply(params["lm_head"], x, head_config(jcfg))
    return logits, new_cache
