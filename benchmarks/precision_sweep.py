"""A/B benchmark: fp32 vs bf16 precision policy over kernel x impl.

Four tables (``name,us_per_call,derived`` rows like every benchmark):

  ps/gemm/<shape>/<kernel>/<prec>   y = x @ w.T single device: the MXU
                                    rate claim (bf16 ~2x on real TPU)
  ps/ring/<impl>/<kernel>/<prec>    jigsaw_linear on an 8-way host mesh:
                                    wall clock per call, both precisions
  ps/wire/<impl>                    lowered-HLO wire bytes fp32 vs bf16
                                    (must be ratio 0.5 -- ASSERTED; read
                                    pre-optimization because the CPU
                                    backend widens bf16 collectives)
  ps/schedule/<impl>/<prec>         analytic per-hop accounting
                                    (comm_schedule_jigsaw_1d): bf16
                                    halves bytes_per_hop at identical
                                    flops_per_hop -> 2x overlap headroom

On CPU the wall-clock rows track code paths, not performance (pallas is
interpret mode, bf16 is emulated); the asserted wire ratio and the
analytic schedule carry the perf claims.  The backend is recorded in
every derived field.

Writes results/precision_sweep.csv unless --tiny (CI smoke) or
--no-write.
"""
import argparse
import os
import sys
import time

if __package__ in (None, ""):   # `python benchmarks/precision_sweep.py`
    sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))

from benchmarks.common import emit, run_subprocess_devices

RESULTS = os.path.join(os.path.dirname(__file__), "..", "results",
                       "precision_sweep.csv")

RING_CODE = """
import time, jax, jax.numpy as jnp
from repro.core.api import JigsawConfig, linear_apply, linear_init
from repro.launch.analysis import collective_stats
from repro.launch.mesh import make_host_mesh

B, T, D, M, ITERS = {b}, {t}, {d}, {m}, {iters}
mesh = make_host_mesh(model=8, data=1)
params = linear_init(jax.random.PRNGKey(0), D, M)
x = jax.random.normal(jax.random.PRNGKey(1), (B, T, D))
with jax.set_mesh(mesh):
    for impl in ["rs", "ring_chunked"]:
        wire = {{}}
        for prec, cd in [("fp32", None), ("bf16", jnp.bfloat16)]:
            for kern in (["xla", "pallas"] if {with_pallas} else ["xla"]):
                cfg = JigsawConfig(impl=impl, kernel=kern,
                                   compute_dtype=cd)
                fn = jax.jit(lambda p, v, c=cfg: linear_apply(p, v, c))
                if kern == "xla":
                    low = fn.lower(params, x)
                    st = collective_stats(
                        low.compiler_ir(dialect="hlo").as_hlo_text())
                    wire[prec] = st.total_bytes
                fn(params, x).block_until_ready()
                t0 = time.time()
                for _ in range(ITERS):
                    fn(params, x).block_until_ready()
                us = (time.time() - t0) / ITERS * 1e6
                print(f"RING {{impl}} {{kern}} {{prec}} {{us:.0f}}")
        ratio = wire["bf16"] / wire["fp32"]
        assert abs(ratio - 0.5) < 1e-6, (impl, wire)
        print(f"WIRE {{impl}} {{wire['fp32']:.0f}} {{wire['bf16']:.0f}} "
              f"{{ratio:.3f}}")
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

    # --- single-device GEMM A/B: fp32 vs bf16, xla vs pallas ----------
    shapes = [(128, 128, 256)] if tiny else [(256, 512, 1024),
                                             (512, 512, 2048)]
    for m, k, n in shapes:
        k1, k2 = jax.random.split(jax.random.PRNGKey(0))
        flops = 2.0 * m * k * n
        for prec, dt in (("fp32", jnp.float32), ("bf16", jnp.bfloat16)):
            x = jax.random.normal(k1, (m, k)).astype(dt)
            w = (jax.random.normal(k2, (n, k)) * 0.05).astype(dt)

            def xla_gemm(x, w):
                return jax.lax.dot_general(
                    x, w, (((1,), (1,)), ((), ())),
                    preferred_element_type=jnp.float32).astype(x.dtype)

            t_x = _timed(jax.jit(xla_gemm), x, w, iters=iters)
            t_p = _timed(lambda x, w: ops.matmul(x, w, None), x, w,
                         iters=iters)
            for kern, t in (("xla", t_x), ("pallas", t_p)):
                rows.append((f"ps/gemm/{m}x{k}x{n}/{kern}/{prec}",
                             int(t * 1e6),
                             f"gflops={flops / t / 1e9:.1f}|mode={mode}"))

    # --- ring sweep on an 8-way host mesh (child above) ----------------
    for line in ring_out.splitlines():
        if line.startswith("RING"):
            _, impl, kern, prec, us = line.split()
            rows.append((f"ps/ring/{impl}/{kern}/{prec}", int(float(us)),
                         f"shape={b_}x{t_}x{d_}x{m_}|mode={mode}"))
        elif line.startswith("WIRE"):
            _, impl, f32b, bf16b, ratio = line.split()
            rows.append((f"ps/wire/{impl}", 0,
                         f"fp32_bytes={f32b}|bf16_bytes={bf16b}"
                         f"|ratio={ratio}|asserted=0.5"))

    # --- analytic per-hop schedule: bf16 doubles overlap headroom -----
    tokens, m, d, p = 4096, 4320, 4320, 8
    for prec, dtype_bytes in (("fp32", 4), ("bf16", 2)):
        for chunked in (False, True):
            cs = comm_schedule_jigsaw_1d(tokens, m, d // p, p,
                                         dtype_bytes=dtype_bytes,
                                         chunked=chunked)
            ratio = cs.overlap_ratio(A.ICI_BW, A.PEAK_FLOPS_BF16)
            rows.append((f"ps/schedule/{cs.scheme}/{prec}", 0,
                         f"hops={cs.hops}"
                         f"|bytes_per_hop={cs.bytes_per_hop:.0f}"
                         f"|flops_per_hop={cs.flops_per_hop:.2e}"
                         f"|overlap_ratio={ratio:.2f}"))
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
        print(f"[precision_sweep] wrote {args.out}")


if __name__ == "__main__":
    main()
