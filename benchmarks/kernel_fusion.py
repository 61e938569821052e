"""A/B benchmark: Pallas fused GEMMs vs XLA, serial vs chunked ring.

Three tables (``name,us_per_call,derived`` rows like every benchmark):

  kf/gemm/<shape>/{xla,pallas}       y = gelu(x @ w.T + b), one device
  kf/mlp/<shape>/{xla,pallas}        the mixer MLP: unfused vs fused
                                     two-GEMM (ops.mixer_mlp)
  kf/ring/<impl>[/pallas]            jigsaw_linear on an 8-way host mesh:
                                     rs vs ring vs ring_chunked
  kf/roofline/ring*                  analytic per-hop overlap accounting
                                     (comm_schedule_jigsaw_1d) at v5e BW

On CPU the pallas rows run in INTERPRET mode: they track the code path
for regressions, not performance (the fig7 roofline model carries the
analytic perf claims; on a real TPU the same script measures compiled
kernels).  The backend is recorded in every derived field.

Writes the table to results/kernel_fusion.csv unless --tiny (CI smoke)
or --no-write is given.
"""
import argparse
import os
import sys
import time

if __package__ in (None, ""):   # `python benchmarks/kernel_fusion.py`
    sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))

from benchmarks.common import emit, run_subprocess_devices

RESULTS = os.path.join(os.path.dirname(__file__), "..", "results",
                       "kernel_fusion.csv")

RING_CODE = """
import time, jax, jax.numpy as jnp
from repro.core.api import JigsawConfig, linear_apply, linear_init
from repro.launch.mesh import make_host_mesh

B, T, D, M, ITERS = {b}, {t}, {d}, {m}, {iters}
mesh = make_host_mesh(model=8, data=1)
params = linear_init(jax.random.PRNGKey(0), D, M)
x = jax.random.normal(jax.random.PRNGKey(1), (B, T, D))
with jax.set_mesh(mesh):
    for impl, kern in [("rs", "xla"), ("ring", "xla"),
                       ("ring_chunked", "xla"),
                       ("ring_chunked", "pallas"),
                       ("ring_fused", "xla"),
                       ("ring_fused", "pallas")]:
        if kern == "pallas" and not {with_pallas}:
            continue
        cfg = JigsawConfig(impl=impl, kernel=kern)
        fn = jax.jit(lambda p, v: linear_apply(p, v, cfg))
        fn(params, x).block_until_ready()
        t0 = time.time()
        for _ in range(ITERS):
            fn(params, x).block_until_ready()
        us = (time.time() - t0) / ITERS * 1e6
        print(f"RING {{impl}} {{kern}} {{us:.0f}}")
"""


def _timed(fn, *args, iters=5):
    import jax
    jax.block_until_ready(fn(*args))
    t0 = time.time()
    for _ in range(iters):
        jax.block_until_ready(fn(*args))
    return (time.time() - t0) / iters


def run(tiny: bool = False):
    import jax
    import jax.numpy as jnp
    from repro.core.api import JigsawConfig, mlp_apply, mlp_init
    from repro.core.jigsaw import comm_schedule_jigsaw_1d
    from repro.kernels import ops
    from repro.launch import analysis as A

    iters = 2 if tiny else 5
    # ring schedules on an 8-way host mesh, in a child started BEFORE this
    # process first touches its JAX backend (one process per chip)
    b_, t_, d_, m_ = (2, 32, 128, 128) if tiny else (4, 256, 512, 512)
    ring_out = run_subprocess_devices(
        RING_CODE.format(b=b_, t=t_, d=d_, m=m_, iters=iters,
                         with_pallas=not tiny), 8)
    backend = jax.default_backend()
    mode = "compiled" if backend == "tpu" else "cpu-interpret"
    rows = []

    # --- single-GEMM A/B: bias + GELU epilogue ------------------------
    shapes = [(128, 128, 256)] if tiny else [(256, 512, 1024),
                                             (512, 512, 2048)]
    for m, k, n in shapes:
        k1, k2, k3 = jax.random.split(jax.random.PRNGKey(0), 3)
        x = jax.random.normal(k1, (m, k))
        w = jax.random.normal(k2, (n, k)) * 0.05
        b = jax.random.normal(k3, (n,)) * 0.1
        flops = 2.0 * m * k * n

        def xla_gemm(x, w, b):
            return jax.nn.gelu(
                jax.lax.dot_general(x, w, (((1,), (1,)), ((), ())),
                                    preferred_element_type=jnp.float32)
                + b[None, :]).astype(x.dtype)

        t_x = _timed(jax.jit(xla_gemm), x, w, b, iters=iters)
        t_p = _timed(lambda x, w, b: ops.matmul(x, w, b, epilogue="gelu"),
                     x, w, b, iters=iters)
        for name, t in (("xla", t_x), ("pallas", t_p)):
            rows.append((f"kf/gemm/{m}x{k}x{n}/{name}", int(t * 1e6),
                         f"gflops={flops / t / 1e9:.1f}|mode={mode}"))

    # --- mixer MLP A/B: unfused vs fused two-GEMM ---------------------
    mshapes = [(64, 128, 128)] if tiny else [(256, 512, 1024)]
    for rows_m, d_in, d_h in mshapes:
        params = mlp_init(jax.random.PRNGKey(1), d_in, d_h, d_in)
        x = jax.random.normal(jax.random.PRNGKey(2), (2, rows_m, d_in))
        flops = 2.0 * 2 * rows_m * d_in * d_h * 2
        for name, cfg in (("xla", JigsawConfig(scheme="none")),
                          ("pallas", JigsawConfig(scheme="none",
                                                  kernel="pallas"))):
            t = _timed(jax.jit(lambda p, v, c=cfg: mlp_apply(p, v, c)),
                       params, x, iters=iters)
            rows.append((f"kf/mlp/{rows_m}x{d_in}x{d_h}/{name}",
                         int(t * 1e6),
                         f"gflops={flops / t / 1e9:.1f}|mode={mode}"))

    # --- ring schedules on an 8-way host mesh (child above) ------------
    for line in ring_out.splitlines():
        if line.startswith("RING"):
            _, impl, kern, us = line.split()
            tag = f"kf/ring/{impl}" + ("" if kern == "xla" else f"/{kern}")
            rows.append((tag, int(float(us)),
                         f"shape={b_}x{t_}x{d_}x{m_}|mode={mode}"))

    # --- analytic per-hop overlap (the fused ring's point) ------------
    # ring: zero overlappable work; ring_chunked: one chunk GEMM exposed
    # per hop, but GEMM and hop are separate HLOs (XLA-best-effort);
    # ring_fused: the same chunk GEMM + the hop add executed INSIDE the
    # kernel while the RDMA flies -- guaranteed overlap.  The fused rows
    # are the schedule the TPU kernel enforces; on this CPU host they are
    # analytic only (see results/ caveat).
    tokens, m, d, p = 4096, 4320, 4320, 8
    for impl in ("ring", "ring_chunked", "ring_fused"):
        cs = comm_schedule_jigsaw_1d(tokens, m, d // p, p, impl=impl)
        ratio = cs.overlap_ratio(A.ICI_BW, A.PEAK_FLOPS_BF16)
        guar = "in-kernel" if impl == "ring_fused" else \
            ("xla-best-effort" if impl == "ring_chunked" else "none")
        rows.append((f"kf/roofline/{cs.scheme}", 0,
                     f"hops={cs.hops}|bytes_per_hop={cs.bytes_per_hop:.0f}"
                     f"|flops_per_hop={cs.flops_per_hop:.2e}"
                     f"|overlap_ratio={ratio:.2f}|overlap={guar}"))
    return rows


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--tiny", action="store_true",
                    help="CI smoke: small shapes, no results/ write")
    ap.add_argument("--no-write", action="store_true")
    ap.add_argument("--out", default=RESULTS)
    args = ap.parse_args()
    rows = run(tiny=args.tiny)
    emit(rows)
    if not args.tiny and not args.no_write:
        with open(args.out, "w") as f:
            f.write("name,us_per_call,derived\n")
            for r in rows:
                f.write(",".join(str(x) for x in r) + "\n")
        print(f"[kernel_fusion] wrote {args.out}")


if __name__ == "__main__":
    main()
