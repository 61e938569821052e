import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"
"""Multi-pod dry-run: prove every (arch x shape x mesh) lowers+compiles.

The two lines above MUST run before any other import (jax locks the
device count on first init).  512 placeholder host devices cover both the
single-pod (16x16) and multi-pod (2x16x16) production meshes.

Usage:
  PYTHONPATH=src python -m repro.launch.dryrun --arch internlm2-1.8b \
      --shape train_4k [--multi-pod] [--scheme 1d] [--impl rs] \
      [--out results.jsonl]
  PYTHONPATH=src python -m repro.launch.dryrun --all [--out results.jsonl]

For each combination it prints ``memory_analysis()`` (the fits-in-HBM
proof) and the roofline terms (analysis.py), and appends a JSON record.
"""
import argparse
import json
import time
import traceback
from functools import partial

import jax
import jax.numpy as jnp

from repro.configs.registry import ARCH_IDS, get_config
from repro.core import jigsaw
from repro.launch import analysis as A
from repro.launch import shapes as SH
from repro.launch.mesh import (make_production_mesh, make_production_mesh_2d)
from repro.models import registry as M
from repro.optim import adam
from repro.serve.step import make_serve_step
from repro.train.step import make_train_step


def build_step_and_args(cfg, shape, mesh, rules, jcfg, zero1=False):
    """Returns (fn, args tuple of ShapeDtypeStructs)."""
    if shape.kind == "train":
        pstructs, pspecs = SH.param_structs(cfg, mesh, rules)
        acfg = adam.AdamConfig(state_dtype=cfg.param_dtype)
        ostructs, _ = SH.opt_structs(pstructs, pspecs, cfg, mesh, acfg,
                                     zero1=zero1)
        batch = SH.input_specs(cfg, shape, mesh, rules)
        return make_train_step(cfg, jcfg, adam_cfg=acfg), \
            (pstructs, ostructs, batch)
    if shape.kind == "prefill":
        pstructs, _ = SH.param_structs(cfg, mesh, rules)
        batch = SH.input_specs(cfg, shape, mesh, rules)

        def prefill_step(params, b):
            if cfg.family == "mixer":
                out, _ = M.apply(params, b, cfg, jcfg)
                return out
            logits, _ = M.apply(params, b, cfg, jcfg)
            return jnp.argmax(logits[:, -1], axis=-1)

        return prefill_step, (pstructs, batch)
    # decode
    pstructs, _ = SH.param_structs(cfg, mesh, rules)
    cstructs, _ = SH.cache_structs(cfg, shape, mesh, rules)
    batch = SH.input_specs(cfg, shape, mesh, rules)
    return make_serve_step(cfg, jcfg), (pstructs, cstructs, batch["tokens"])


def tree_bytes(tree) -> int:
    return sum(x.size * x.dtype.itemsize for x in jax.tree.leaves(tree))


def run_one(arch: str, shape_name: str, *, multi_pod: bool = False,
            scheme=None, impl=None, remat=None, q_chunk=None,
            kv_shard=None, zero1: bool = False, verbose: bool = True):
    cfg = get_config(arch)
    if scheme:
        cfg = cfg.replace(scheme=scheme)
    if impl:
        cfg = cfg.replace(impl=impl)
    if remat is not None:
        cfg = cfg.replace(remat=remat)
    if q_chunk is not None:
        cfg = cfg.replace(attn_q_chunk=q_chunk)
    if kv_shard is not None:
        cfg = cfg.replace(kv_shard=kv_shard)
    shape = SH.SHAPES[shape_name]
    if cfg.family == "mixer":
        # WM token-mix weights are [d_tok, T]: the model is tied to its
        # grid, so each input shape instantiates the arch AT that grid
        # (train_4k: 512x512 = 4096 tokens; prefill_32k: 1456x1440 ~= the
        # paper's own 0.25-degree resolution).
        lat, lon = SH.mixer_grid_for(shape, cfg)
        cfg = cfg.replace(wm_lat=lat, wm_lon=lon)
    ok, reason = SH.applicable(cfg, shape)
    rec = {"arch": arch, "shape": shape_name, "scheme": cfg.scheme,
           "impl": cfg.impl, "multi_pod": multi_pod, "zero1": zero1,
           "q_chunk": cfg.attn_q_chunk, "kv_shard": cfg.kv_shard}
    if not ok:
        rec.update(status="SKIP", reason=reason)
        if verbose:
            print(f"[skip] {arch} x {shape_name}: {reason}")
        return rec

    mesh = (make_production_mesh_2d(multi_pod=multi_pod)
            if cfg.scheme == "2d"
            else make_production_mesh(multi_pod=multi_pod))
    rules = SH.rules_for(cfg)
    if multi_pod:
        import dataclasses as dc
        rules = dc.replace(rules, batch_axes=("pod",) + rules.batch_axes)
    jcfg = SH.jigsaw_for(cfg).replace(rules=rules)
    n_dev = mesh.size
    t0 = time.time()
    try:
        with jax.set_mesh(mesh):
            fn, args = build_step_and_args(cfg, shape, mesh, rules, jcfg,
                                           zero1=zero1)
            lowered = jax.jit(fn).lower(*args)
            t_lower = time.time() - t0
            compiled = lowered.compile()
            t_compile = time.time() - t0 - t_lower
    except Exception as e:
        rec.update(status="FAIL", error=f"{type(e).__name__}: {e}",
                   traceback=traceback.format_exc()[-2000:])
        if verbose:
            print(f"[FAIL] {arch} x {shape_name} "
                  f"({'multi' if multi_pod else 'single'}-pod): {e}")
        return rec

    ma = compiled.memory_analysis()
    print(f"# {arch} x {shape_name} "
          f"({'2x16x16' if multi_pod else '16x16'}, scheme={cfg.scheme}, "
          f"impl={cfg.impl})")
    print(f"  memory_analysis: {ma}")

    # roofline terms
    param_bytes = tree_bytes(args[0])
    opt_bytes = tree_bytes(args[1]) if shape.kind == "train" else 0
    cache_bytes = tree_bytes(args[1]) if shape.kind == "decode" else 0
    b, s = shape.global_batch, shape.seq_len
    if cfg.family == "mixer":
        lat, lon = SH.mixer_grid_for(shape, cfg)
        s = (lat // cfg.wm_patch) * (lon // cfg.wm_patch)
    flops_total = A.flops_step(cfg, shape.kind, b, s)
    hbm_total = A.hbm_bytes_step(cfg, shape.kind, b, s, param_bytes,
                                 cache_bytes, opt_bytes)
    stats = A.collective_stats(compiled.as_text())
    ca = compiled.cost_analysis() or {}
    tokens = b * (s if shape.kind != "decode" else 1)
    if cfg.family == "mixer":
        # 6*N*D is a dense-LM heuristic; WM's token-mix params scale with
        # T, so MODEL_FLOPS is the forward matmul work itself (x3 for
        # train fwd+bwd) -- useful_ratio then exposes the remat factor.
        fwd = sum(A.flops_forward(cfg, b, s).values())
        mf = 3.0 * fwd if shape.kind == "train" else fwd
    else:
        mf = (A.model_flops_train(cfg, tokens) if shape.kind == "train"
              else A.model_flops_decode(cfg, b) if shape.kind == "decode"
              else A.model_flops_train(cfg, tokens) / 3.0)
    comp_s = flops_total / n_dev / A.PEAK_FLOPS_BF16
    mem_s = hbm_total / n_dev / A.HBM_BW
    coll_s = stats.total_bytes / A.ICI_BW
    terms = {"compute_s": comp_s, "memory_s": mem_s, "collective_s": coll_s}
    bottleneck = max(terms, key=terms.get).replace("_s", "")
    rec.update(
        status="OK", n_devices=n_dev,
        lower_s=round(t_lower, 1), compile_s=round(t_compile, 1),
        arg_gib=round(ma.argument_size_in_bytes / 2**30, 3),
        temp_gib=round(ma.temp_size_in_bytes / 2**30, 3),
        out_gib=round(ma.output_size_in_bytes / 2**30, 3),
        fits_hbm=bool((ma.argument_size_in_bytes + ma.temp_size_in_bytes
                       + ma.output_size_in_bytes) < 16 * 2**30),
        param_bytes_total=param_bytes, opt_bytes_total=opt_bytes,
        cache_bytes_total=cache_bytes,
        flops_per_dev=flops_total / n_dev,
        hbm_bytes_per_dev=hbm_total / n_dev,
        collective_bytes_per_dev=stats.total_bytes,
        collective_counts=stats.counts,
        xla_entry_flops=float(ca.get("flops", 0.0)),
        compute_s=comp_s, memory_s=mem_s, collective_s=coll_s,
        bottleneck=bottleneck,
        model_flops=mf, useful_ratio=(mf / flops_total if flops_total else 0),
    )
    if verbose:
        print(f"  flops/dev={flops_total / n_dev:.3e}  "
              f"hbm/dev={hbm_total / n_dev:.3e}B  "
              f"coll/dev={stats.total_bytes:.3e}B")
        print(f"  roofline: compute={comp_s * 1e3:.2f}ms  "
              f"memory={mem_s * 1e3:.2f}ms  collective={coll_s * 1e3:.2f}ms"
              f"  -> {bottleneck}-bound; "
              f"useful={rec['useful_ratio'] * 100:.0f}%")
        print(f"  collectives: {stats.counts}")
    return rec


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default=None, choices=ARCH_IDS + [None])
    ap.add_argument("--shape", default=None,
                    choices=list(SH.SHAPES) + [None])
    ap.add_argument("--multi-pod", action="store_true")
    ap.add_argument("--both-meshes", action="store_true")
    ap.add_argument("--all", action="store_true",
                    help="every (arch x shape), single+multi pod")
    ap.add_argument("--scheme", default=None, choices=["1d", "2d", "none"])
    ap.add_argument("--impl", default=None, choices=jigsaw.Impl1D)
    ap.add_argument("--no-remat", action="store_true")
    ap.add_argument("--q-chunk", type=int, default=None,
                    help="chunked attention query-block size (beyond-paper)")
    ap.add_argument("--kv-shard", default=None,
                    choices=["auto", "heads", "seq", "headdim"])
    ap.add_argument("--zero1", action="store_true",
                    help="ZeRO-1: shard optimizer moments over data too")
    ap.add_argument("--out", default=None, help="append JSONL records here")
    args = ap.parse_args()

    combos = []
    archs = ARCH_IDS if (args.all or not args.arch) else [args.arch]
    shapes = list(SH.SHAPES) if (args.all or not args.shape) \
        else [args.shape]
    meshes = [False, True] if (args.all or args.both_meshes) \
        else [args.multi_pod]
    for a in archs:
        for sh in shapes:
            for mp in meshes:
                combos.append((a, sh, mp))

    results = []
    for a, sh, mp in combos:
        rec = run_one(a, sh, multi_pod=mp, scheme=args.scheme,
                      impl=args.impl, q_chunk=args.q_chunk,
                      kv_shard=args.kv_shard, zero1=args.zero1,
                      remat=False if args.no_remat else None)
        results.append(rec)
        if args.out:
            with open(args.out, "a") as f:
                f.write(json.dumps(rec) + "\n")

    n_ok = sum(r["status"] == "OK" for r in results)
    n_skip = sum(r["status"] == "SKIP" for r in results)
    n_fail = sum(r["status"] == "FAIL" for r in results)
    print(f"\n== dry-run summary: {n_ok} OK, {n_skip} SKIP (documented), "
          f"{n_fail} FAIL ==")
    if n_fail:
        for r in results:
            if r["status"] == "FAIL":
                print(f"  FAIL {r['arch']} x {r['shape']} "
                      f"mp={r['multi_pod']}: {r['error']}")
        raise SystemExit(1)


if __name__ == "__main__":
    main()
