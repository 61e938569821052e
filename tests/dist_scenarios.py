"""Distributed-correctness scenarios (run in a subprocess with
XLA_FLAGS=--xla_force_host_platform_device_count=16 by test_distributed.py;
NOT collected by pytest directly).

Each scenario asserts numerical equivalence between a Jigsaw-distributed
computation and its dense single-device reference -- the paper's own
correctness invariant (Fig. 4: "equivalent architectures across 1-, 2-,
4-way parallel models").
"""
import os
import sys

if __name__ == "__main__":
    os.environ.setdefault("XLA_FLAGS",
                          "--xla_force_host_platform_device_count=16")

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))

import numpy as np  # noqa: E402
import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
from jax.sharding import NamedSharding, PartitionSpec as P  # noqa: E402

from repro.core import jigsaw  # noqa: E402
from repro.core.api import JigsawConfig, linear_apply, linear_init  # noqa: E402
from repro.core.sharding import RULES_1D, RULES_2D  # noqa: E402
from repro.launch.mesh import make_host_mesh  # noqa: E402

AUTO = (jax.sharding.AxisType.Auto,)


def _loss(p, x, cfg):
    return jnp.sum(linear_apply(p, x, cfg) ** 2)


# XLA's CPU backend picks its fp32 dot reduction order from the output
# width, so a ring chunk's GEMM (N = m/p) and the monolithic GEMM (N = m)
# can differ in the last bits: up to ~8 fp32 ulp of the tensor's largest
# magnitude at these sizes.  With the Pallas GEMM (fixed tile shape) or
# bf16 compute the ring variants stay bit-identical (np.array_equal).
DOT_ORDER_TOL = 1e-6


def dot_order_close(a, b):
    a, b = np.asarray(a), np.asarray(b)
    return np.allclose(a, b, rtol=0,
                       atol=DOT_ORDER_TOL * float(np.max(np.abs(b))))


def check(name, ok):
    print(f"  [{'ok' if ok else 'FAIL'}] {name}")
    if not ok:
        raise AssertionError(name)


def scenario_jigsaw_1d():
    """1-D Jigsaw (2-way paper scheme generalized to 8-way): fwd + grads
    equal dense for every impl."""
    mesh = make_host_mesh(model=8, data=2)
    params = linear_init(jax.random.PRNGKey(0), 64, 128)
    x = jax.random.normal(jax.random.PRNGKey(1), (4, 16, 64))
    ref_v, ref_g = jax.value_and_grad(_loss)(params, x,
                                             JigsawConfig(scheme="none"))
    with jax.set_mesh(mesh):
        for impl in ["ring", "ring_chunked", "rs", "allreduce", "gspmd"]:
            v, g = jax.jit(jax.value_and_grad(_loss), static_argnums=2)(
                params, x, JigsawConfig(impl=impl))
            ok = np.allclose(v, ref_v, rtol=1e-4) and all(
                np.allclose(g[k], ref_g[k], rtol=1e-3, atol=1e-4)
                for k in ("w", "b"))
            check(f"1d impl={impl} fwd+grad == dense", ok)


def scenario_jigsaw_1d_fsdp():
    """FSDP-hybrid (w also sharded over data) matches dense."""
    mesh = make_host_mesh(model=4, data=4)
    params = linear_init(jax.random.PRNGKey(0), 64, 128)
    x = jax.random.normal(jax.random.PRNGKey(1), (8, 16, 64))
    ref_v, ref_g = jax.value_and_grad(_loss)(params, x,
                                             JigsawConfig(scheme="none"))
    with jax.set_mesh(mesh):
        cfg = JigsawConfig(impl="rs", fsdp=True)
        pp = {"w": jax.device_put(params["w"],
                                  NamedSharding(mesh, P("data", "model"))),
              "b": jax.device_put(params["b"],
                                  NamedSharding(mesh, P("model")))}
        v, g = jax.jit(jax.value_and_grad(_loss), static_argnums=2)(
            pp, x, cfg)
        ok = np.allclose(v, ref_v, rtol=1e-4) and all(
            np.allclose(g[k], ref_g[k], rtol=1e-3, atol=1e-4)
            for k in ("w", "b"))
        check("1d fsdp fwd+grad == dense", ok)


def scenario_jigsaw_2d():
    """2-D Jigsaw (4-way paper scheme at 2x2, generalized at 4x4):
    Cannon fwd + grads equal dense; transposed variant too."""
    params = linear_init(jax.random.PRNGKey(0), 64, 128)
    x = jax.random.normal(jax.random.PRNGKey(1), (4, 16, 64))
    ref_v, ref_g = jax.value_and_grad(_loss)(params, x,
                                             JigsawConfig(scheme="none"))
    for q, model in [(2, 4), (4, 16)]:
        data = 16 // model if model < 16 else 1
        mesh = jax.make_mesh((data, q, q), ("data", "mdom", "mtp"),
                             axis_types=AUTO * 3)
        with jax.set_mesh(mesh):
            cfg = JigsawConfig(rules=RULES_2D, scheme="2d")
            v, g = jax.jit(jax.value_and_grad(_loss), static_argnums=2)(
                params, x, cfg)
            ok = np.allclose(v, ref_v, rtol=1e-4) and all(
                np.allclose(g[k], ref_g[k], rtol=1e-3, atol=1e-4)
                for k in ("w", "b"))
            check(f"2d cannon {q}x{q} fwd+grad == dense", ok)

    # transposed Cannon (token-mixing): y = w @ x over dim -2
    w = jax.random.normal(jax.random.PRNGKey(2), (32, 16)) * 0.1
    bias = jax.random.normal(jax.random.PRNGKey(3), (32,)) * 0.1
    ref = jnp.einsum("mt,btc->bmc", w, x) + bias[None, :, None]
    mesh = jax.make_mesh((1, 4, 4), ("data", "mdom", "mtp"),
                         axis_types=AUTO * 3)
    with jax.set_mesh(mesh):
        y = jax.jit(lambda xx, ww, bb: jigsaw.jigsaw_linear_2d_t(
            xx, ww, bb, rules=RULES_2D))(x, w, bias)
    check("2d_t cannon 4x4 (transposed MLP) == dense",
          np.allclose(y, ref, rtol=1e-4, atol=1e-5))


def scenario_ring_chunked_parity():
    """Interpret-mode parity of the chunked ring and the Pallas kernel
    path: ring_chunked == ring (identical chunk walk: bit-for-bit under
    bf16, within DOT_ORDER_TOL under fp32 XLA GEMMs), == rs within f32
    reduction-order tolerance; kernel="pallas"
    matches kernel="xla" for fwd AND grads (AD through the chunked ring
    runs the custom-VJP backward GEMMs)."""
    mesh = make_host_mesh(model=8, data=2)
    params = linear_init(jax.random.PRNGKey(0), 64, 128)
    x = jax.random.normal(jax.random.PRNGKey(1), (4, 16, 64))
    ref_v, ref_g = jax.value_and_grad(_loss)(params, x,
                                             JigsawConfig(scheme="none"))
    with jax.set_mesh(mesh):
        outs = {}
        for impl in ("ring", "ring_chunked", "rs"):
            outs[impl] = np.asarray(jax.jit(linear_apply, static_argnums=2)(
                params, x, JigsawConfig(impl=impl)))
        check("ring_chunked == ring (fp32, CPU dot-order tolerance)",
              dot_order_close(outs["ring_chunked"], outs["ring"]))
        bf = {impl: np.asarray(jax.jit(linear_apply, static_argnums=2)(
            params, x, JigsawConfig(impl=impl, compute_dtype=jnp.bfloat16)))
            for impl in ("ring", "ring_chunked")}
        check("ring_chunked == ring bit-for-bit (bf16)",
              np.array_equal(bf["ring_chunked"], bf["ring"]))
        check("ring_chunked == rs (f32 reduction tolerance)",
              np.allclose(outs["ring_chunked"], outs["rs"],
                          rtol=1e-6, atol=1e-6))
        # AD through the chunked ring
        v, g = jax.jit(jax.value_and_grad(_loss), static_argnums=2)(
            params, x, JigsawConfig(impl="ring_chunked"))
        ok = np.allclose(v, ref_v, rtol=1e-4) and all(
            np.allclose(g[k], ref_g[k], rtol=1e-3, atol=1e-4)
            for k in ("w", "b"))
        check("ring_chunked kernel=xla fwd+grad == dense", ok)

    # pallas local GEMMs: interpret mode is slow, so a 4-way mesh
    mesh4 = make_host_mesh(model=4, data=1)
    with jax.set_mesh(mesh4):
        cfg = JigsawConfig(impl="ring_chunked", kernel="pallas")
        v, g = jax.jit(jax.value_and_grad(_loss), static_argnums=2)(
            params, x, cfg)
        ok = np.allclose(v, ref_v, rtol=1e-4) and all(
            np.allclose(g[k], ref_g[k], rtol=1e-3, atol=1e-4)
            for k in ("w", "b"))
        check("ring_chunked kernel=pallas fwd+grad == dense", ok)
        y = jax.jit(linear_apply, static_argnums=2)(
            params, x, JigsawConfig(impl="rs", kernel="pallas"))
        yx = jax.jit(linear_apply, static_argnums=2)(
            params, x, JigsawConfig(impl="rs"))
        check("rs kernel=pallas == xla",
              np.allclose(np.asarray(y), np.asarray(yx),
                          rtol=1e-5, atol=1e-5))

    # 2-D Cannon with pallas local blocks (paper's 4-way at 2x2)
    mesh2 = jax.make_mesh((1, 2, 2), ("data", "mdom", "mtp"),
                          axis_types=AUTO * 3)
    with jax.set_mesh(mesh2):
        cfg2 = JigsawConfig(rules=RULES_2D, scheme="2d", kernel="pallas")
        v, g = jax.jit(jax.value_and_grad(_loss), static_argnums=2)(
            params, x, cfg2)
        ok = np.allclose(v, ref_v, rtol=1e-4) and all(
            np.allclose(g[k], ref_g[k], rtol=1e-3, atol=1e-4)
            for k in ("w", "b"))
        check("2d cannon kernel=pallas fwd+grad == dense", ok)


def scenario_ring_fused_parity():
    """The one-kernel ring: impl="ring_fused" must be BIT-identical to
    impl="ring" -- forward and grads -- under fp32 and bf16 policies and
    both local-GEMM engines, except fp32 XLA GEMMs, which agree within
    DOT_ORDER_TOL (the acceptance criterion;
    on CPU this exercises the deterministic chunk-granular fallback whose
    cast points mirror the TPU kernel's).  Also: the Pallas transposed
    Cannon (jigsaw_linear_2d_t kernel="pallas") vs the dot_general
    lowering, the VMEM-budget guard, and a 2-step TrainEngine A/B."""
    from repro.kernels import fused_ring

    params = linear_init(jax.random.PRNGKey(0), 64, 128)
    x = jax.random.normal(jax.random.PRNGKey(1), (4, 16, 64))

    def run(impl, kern, cd):
        cfg = JigsawConfig(impl=impl, kernel=kern, compute_dtype=cd)
        v, g = jax.jit(jax.value_and_grad(_loss), static_argnums=2)(
            params, x, cfg)
        return v, g

    mesh = make_host_mesh(model=8, data=2)
    with jax.set_mesh(mesh):
        for cd in (None, jnp.bfloat16):
            # fp32 through XLA's CPU dot: equal up to its dot order only
            same, how = ((dot_order_close, "CPU dot-order tolerance")
                         if cd is None else (np.array_equal, "bit-for-bit"))
            tag = "bf16" if cd is not None else "fp32"
            v0, g0 = run("ring", "xla", cd)
            v1, g1 = run("ring_fused", "xla", cd)
            ok = same(v1, v0) and all(same(g1[k], g0[k])
                                      for k in ("w", "b"))
            check(f"ring_fused == ring fwd+grads, {how} ({tag})", ok)

    # pallas local GEMMs (interpret mode is slow -> 4-way mesh)
    mesh4 = make_host_mesh(model=4, data=1)
    with jax.set_mesh(mesh4):
        for cd in (None, jnp.bfloat16):
            tag = "bf16" if cd is not None else "fp32"
            v0, g0 = run("ring", "pallas", cd)
            v1, g1 = run("ring_fused", "pallas", cd)
            ok = np.array_equal(np.asarray(v0), np.asarray(v1)) and all(
                np.array_equal(np.asarray(g0[k]), np.asarray(g1[k]))
                for k in ("w", "b"))
            check(f"ring_fused == ring bit-for-bit, pallas ({tag})", ok)

    # fused transposed Cannon == dot_general lowering (token-mix path)
    wt = jax.random.normal(jax.random.PRNGKey(2), (32, 16)) * 0.1
    bt = jax.random.normal(jax.random.PRNGKey(3), (32,)) * 0.1
    mesh2 = jax.make_mesh((1, 2, 2), ("data", "mdom", "mtp"),
                          axis_types=AUTO * 3)
    with jax.set_mesh(mesh2):
        def tmix(kern, xx, ww, bb):
            y = jigsaw.jigsaw_linear_2d_t(xx, ww, bb, rules=RULES_2D,
                                          kernel=kern)
            return jnp.sum(y ** 2), y
        (_, y0), g0 = jax.jit(lambda *a: jax.value_and_grad(
            lambda xx, ww, bb: tmix("xla", xx, ww, bb), argnums=(0, 1, 2),
            has_aux=True)(*a))(x, wt, bt)
        (_, y1), g1 = jax.jit(lambda *a: jax.value_and_grad(
            lambda xx, ww, bb: tmix("pallas", xx, ww, bb),
            argnums=(0, 1, 2), has_aux=True)(*a))(x, wt, bt)
        check("2d_t cannon kernel=pallas == xla (fwd)",
              np.allclose(y0, y1, rtol=1e-5, atol=1e-5))
        check("2d_t cannon kernel=pallas == xla (grads)",
              all(np.allclose(a, b, rtol=1e-4, atol=1e-4)
                  for a, b in zip(g0, g1)))

    # VMEM-budget guard: over-budget tiles select the fallback (with the
    # one-line warning); in-budget tiles on a TPU backend select the
    # fused kernel.  backend/budget are parameters so this runs on CPU.
    import warnings as _w
    with _w.catch_warnings(record=True) as rec:
        _w.simplefilter("always")
        path = fused_ring._select_path(
            4096, 4096, 65536, 8, jnp.float32, jnp.float32,
            ("data", "model"), "model", backend="tpu", budget=1 << 20)
    check("vmem guard falls back over budget",
          path == "fallback" and any("VMEM" in str(r.message)
                                     for r in rec))
    check("vmem guard keeps the fused kernel in budget",
          fused_ring._select_path(64, 64, 128, 8, jnp.float32, jnp.float32,
                                  ("data", "model"), "model",
                                  backend="tpu") == "tpu")
    check("cpu backend always falls back",
          fused_ring._select_path(64, 64, 128, 8, jnp.float32, jnp.float32,
                                  ("data", "model"), "model") == "fallback")

    # end-to-end: 2 engine steps, fused vs monolithic ring -- identical
    # loss history bit-for-bit (every linear of the model goes through
    # the fused schedule).
    from repro.launch.engine import EngineConfig, TrainEngine

    def engine_losses(impl):
        eng = TrainEngine(
            "weathermixer-1b", mesh_model=4, mesh_data=4, scheme="1d",
            impl=impl,
            config=EngineConfig(steps=2, batch=4, log_every=1))
        eng.run()
        return [h["loss"] for h in eng.history]

    l_ring = engine_losses("ring")
    l_fused = engine_losses("ring_fused")
    check(f"engine 2-step loss history identical ({l_ring} == {l_fused})",
          np.array_equal(np.asarray(l_ring), np.asarray(l_fused)))


def scenario_zero1_engine():
    """ZeRO-1 wired into TrainEngine: loss history identical to the
    replicated-optimizer run, moments actually sharded over data (per-
    device optimizer-state bytes shrink by the data extent)."""
    from repro.launch.engine import EngineConfig, TrainEngine

    def run(zero1):
        eng = TrainEngine(
            "weathermixer-1b", mesh_model=4, mesh_data=4, scheme="1d",
            config=EngineConfig(steps=2, batch=4, log_every=1,
                                zero1=zero1))
        eng.run()
        return eng

    e0 = run(False)
    e1 = run(True)
    ok = all(np.allclose(a["loss"], b["loss"], rtol=1e-5)
             for a, b in zip(e0.history, e1.history))
    check("zero1 loss history == replicated", ok)

    def dev0_moment_bytes(eng):
        dev = jax.devices()[0]
        tot = 0
        for leaf in jax.tree.leaves({"mu": eng.opt_state["mu"],
                                     "nu": eng.opt_state["nu"]}):
            for s in leaf.addressable_shards:
                if s.device == dev:
                    tot += s.data.nbytes
        return tot

    b0, b1 = dev0_moment_bytes(e0), dev0_moment_bytes(e1)
    # data=4: every evenly divisible moment shards 4x; the residue
    # (tiny norms/biases that don't divide) keeps this from being exactly
    # 4x, but the bulk must shrink by >= 2x.
    check(f"zero1 moment bytes shrink ({b0} -> {b1})", b1 * 2 <= b0)
    spec = e1.opt_state["mu"]["blocks"]["ch_fc1"]["w"].sharding.spec
    flat = [a for e in spec if e is not None
            for a in (e if isinstance(e, tuple) else (e,))]
    check("zero1 moment spec carries the data axis", "data" in flat)


def scenario_precision_bf16():
    """Mixed-precision Jigsaw (ISSUE 5): the bf16 policy must (a) track
    the fp32 loss trajectory on the same seed within bf16 tolerance,
    (b) keep fp32 Adam master weights + moments while the donated params
    are bf16, (c) HALVE the ring/`ring_chunked` per-hop wire bytes on
    the lowered HLO, and (d) keep ring == ring_chunked bit-identical
    under the bf16 wire/f32-accum cast points."""
    import jax.numpy as jnp
    from repro.core.api import JigsawConfig, linear_apply, mlp_apply, \
        mlp_init
    from repro.launch.analysis import collective_stats
    from repro.launch.engine import EngineConfig, TrainEngine

    # --- (a)+(b): engine A/B on a 4x2 mesh -----------------------------
    def run(precision):
        eng = TrainEngine(
            "weathermixer-1b", mesh_model=4, mesh_data=2, scheme="1d",
            impl="ring_chunked",
            config=EngineConfig(steps=4, batch=4, log_every=1,
                                precision=precision))
        return eng.run(), eng

    h32, e32 = run(None)
    h16, e16 = run("bf16")
    ok = all(np.allclose(a["loss"], b["loss"], rtol=5e-2, atol=5e-3)
             for a, b in zip(h32, h16))
    check("bf16 loss history ~= fp32 (same seed)", ok)
    # losses must differ somewhere, or the bf16 path silently never ran
    check("bf16 path actually engaged (histories not bit-equal)",
          any(a["loss"] != b["loss"] for a, b in zip(h32, h16)))

    w16 = e16.params["blocks"]["ch_fc1"]["w"]
    check("params stored bf16", w16.dtype == jnp.bfloat16)
    check("Adam master weights are fp32",
          e16.opt_state["master"]["blocks"]["ch_fc1"]["w"].dtype
          == jnp.float32)
    check("Adam moments are fp32 under the bf16 policy",
          e16.opt_state["mu"]["blocks"]["ch_fc1"]["w"].dtype == jnp.float32
          and e16.opt_state["nu"]["blocks"]["ch_fc1"]["w"].dtype
          == jnp.float32)
    check("fp32 run has no master group", "master" not in e32.opt_state)

    # satellite: engine-level param PartitionSpec pinning -- without
    # zero1, params must still come back SHARDED (not GSPMD-replicated)
    spec = w16.sharding.spec
    flat = [a for e in spec if e is not None
            for a in (e if isinstance(e, tuple) else (e,))]
    check("params pinned to jigsaw specs (model axis present, "
          "non-zero1 run)", "model" in flat)

    # --- (c): ring bytes halve on the lowered HLO ----------------------
    mesh = make_host_mesh(model=4, data=1)
    params = mlp_init(jax.random.PRNGKey(0), 64, 256, 64, bias=False)
    x = jax.random.normal(jax.random.PRNGKey(1), (1, 32, 64))
    for impl in ("ring", "ring_chunked"):
        res = {}
        for prec, cd in (("fp32", None), ("bf16", jnp.bfloat16)):
            cfg = JigsawConfig(impl=impl, compute_dtype=cd)
            with jax.set_mesh(mesh):
                low = jax.jit(
                    lambda p, v, c=cfg: mlp_apply(p, v, c)).lower(params, x)
            st = collective_stats(
                low.compiler_ir(dialect="hlo").as_hlo_text())
            res[prec] = st.total_bytes
        check(f"{impl}: bf16 wire bytes == 0.5x fp32 "
              f"({res['bf16']:.0f} vs {res['fp32']:.0f})",
              res["fp32"] > 0 and abs(res["bf16"] / res["fp32"] - 0.5)
              < 1e-6)

    # --- (d): bit-identity + accuracy of the bf16 ring -----------------
    lparams = {"w": jax.random.normal(jax.random.PRNGKey(2), (128, 64))
               * 0.1,
               "b": jax.random.normal(jax.random.PRNGKey(3), (128,)) * 0.1}
    ref = np.asarray(linear_apply(lparams, x, JigsawConfig(scheme="none")))
    with jax.set_mesh(mesh):
        outs = {}
        for impl in ("ring", "ring_chunked", "rs"):
            cfg = JigsawConfig(impl=impl, compute_dtype=jnp.bfloat16)
            outs[impl] = np.asarray(
                jax.jit(linear_apply, static_argnums=2)(lparams, x, cfg)
                .astype(jnp.float32))
        check("bf16 ring_chunked == ring bit-for-bit",
              np.array_equal(outs["ring_chunked"], outs["ring"]))
        check("bf16 ring ~= bf16 rs (wire rounding tolerance)",
              np.allclose(outs["ring_chunked"], outs["rs"], rtol=2e-2,
                          atol=2e-2))
        check("bf16 ring ~= fp32 dense reference",
              np.allclose(outs["ring_chunked"], ref, rtol=5e-2, atol=5e-2))

    # composition: bf16 x ZeRO-1 -- the fp32 masters shard over data
    # like the moments (3 fp32 trees / data-ways per rank)
    engz = TrainEngine(
        "weathermixer-1b", mesh_model=4, mesh_data=2, scheme="1d",
        config=EngineConfig(steps=2, batch=4, log_every=1,
                            precision="bf16", zero1=True))
    hz = engz.run()
    mspec = engz.opt_state["master"]["blocks"]["ch_fc1"]["w"].sharding.spec
    mflat = [a for e in mspec if e is not None
             for a in (e if isinstance(e, tuple) else (e,))]
    check("bf16 x zero1: master weights sharded over data",
          "data" in mflat)
    check("bf16 x zero1: loss tracks the non-zero1 bf16 run",
          np.allclose(hz[0]["loss"], h16[0]["loss"], rtol=1e-3))

    # bf16_pure: memory-minimal -- bf16 moments, no masters
    engp = TrainEngine(
        "weathermixer-1b", mesh_model=4, mesh_data=2, scheme="1d",
        config=EngineConfig(steps=2, batch=4, log_every=1,
                            precision="bf16_pure"))
    engp.run()
    check("bf16_pure: no master group", "master" not in engp.opt_state)
    check("bf16_pure: bf16 moments",
          engp.opt_state["mu"]["blocks"]["ch_fc1"]["w"].dtype
          == jnp.bfloat16)


def scenario_ring_collectives():
    """Explicit ring reduce-scatter / allgather == native collectives."""
    mesh = make_host_mesh(model=8, data=2)
    x = jax.random.normal(jax.random.PRNGKey(0), (4, 64))

    def rs(v):
        return jigsaw.ring_reduce_scatter(v, "model", 8)

    def ag(v):
        return jigsaw.ring_all_gather(v, "model", 8, gather_dim=-1)

    with jax.set_mesh(mesh):
        out = jax.jit(jax.shard_map(
            rs, mesh=mesh, in_specs=P(None, None),
            out_specs=P(None, "model"), axis_names={"model"},
            check_vma=False))(x)
        check("ring_reduce_scatter == 8*chunk",
              np.allclose(out, 8 * x, rtol=1e-5))
        out2 = jax.jit(jax.shard_map(
            ag, mesh=mesh, in_specs=P(None, "model"),
            out_specs=P(None, None), axis_names={"model"},
            check_vma=False))(x)
        check("ring_all_gather roundtrip", np.allclose(out2, x, rtol=1e-6))


def scenario_weathermixer_schemes():
    """WM forward under 1d and 2d Jigsaw == dense (paper Fig. 4)."""
    from repro.configs.registry import get_config
    from repro.models import registry as M
    from repro.launch import shapes as SH

    cfg0 = get_config("weathermixer-1b").reduced()
    key = jax.random.PRNGKey(0)
    params = M.init(key, cfg0)
    batch = {"fields": jax.random.normal(key, (4, cfg0.wm_lat, cfg0.wm_lon,
                                               cfg0.wm_channels))}
    ref, _ = M.apply(params, batch, cfg0, SH.jigsaw_for(cfg0))

    mesh1 = make_host_mesh(model=4, data=4)
    cfg1 = cfg0.replace(scheme="1d")
    with jax.set_mesh(mesh1):
        out1, _ = jax.jit(lambda p, b: M.apply(p, b, cfg1,
                                               SH.jigsaw_for(cfg1)))(
            params, batch)
    check("WM 1d (2-way generalized) == dense",
          np.allclose(out1, ref, rtol=1e-3, atol=1e-4))

    mesh2 = make_host_mesh(model=4, data=1, two_d=True)
    cfg2 = cfg0.replace(scheme="2d")
    with jax.set_mesh(mesh2):
        out2, _ = jax.jit(lambda p, b: M.apply(p, b, cfg2,
                                               SH.jigsaw_for(cfg2)))(
            params, batch)
    check("WM 2d (4-way Cannon) == dense",
          np.allclose(out2, ref, rtol=1e-3, atol=1e-4))


def scenario_transformer_1d():
    """Reduced internlm2 forward under 1-D Jigsaw mesh == dense."""
    from repro.configs.registry import get_config
    from repro.models import registry as M
    from repro.launch import shapes as SH

    cfg0 = get_config("internlm2-1.8b").reduced()
    key = jax.random.PRNGKey(0)
    params = M.init(key, cfg0)
    batch = {"tokens": jax.random.randint(key, (4, 32), 0,
                                          cfg0.vocab_size)}
    ref, _ = M.apply(params, batch, cfg0, SH.jigsaw_for(cfg0))
    mesh = make_host_mesh(model=4, data=4)
    cfg = cfg0.replace(scheme="1d", impl="rs")
    with jax.set_mesh(mesh):
        out, _ = jax.jit(lambda p, b: M.apply(p, b, cfg,
                                              SH.jigsaw_for(cfg)))(
            params, batch)
    check("transformer 1d jigsaw == dense",
          np.allclose(out, ref, rtol=1e-3, atol=1e-3))


def scenario_train_step_mesh():
    """One full train step on a mesh == same step on one device."""
    from repro.configs.registry import get_config
    from repro.models import registry as M
    from repro.launch import shapes as SH
    from repro.optim import adam
    from repro.train.step import make_train_step

    cfg0 = get_config("stablelm-3b").reduced()
    key = jax.random.PRNGKey(0)
    params = M.init(key, cfg0)
    acfg = adam.AdamConfig()
    opt = adam.init(params, acfg)
    batch = {"tokens": jax.random.randint(key, (8, 16), 0, cfg0.vocab_size),
             "labels": jax.random.randint(key, (8, 16), 0, cfg0.vocab_size)}
    p_ref, _, m_ref = make_train_step(cfg0, SH.jigsaw_for(cfg0), acfg)(
        params, opt, batch)
    cfg = cfg0.replace(scheme="1d")
    mesh = make_host_mesh(model=4, data=2)
    with jax.set_mesh(mesh):
        p_new, _, m_new = jax.jit(make_train_step(cfg, SH.jigsaw_for(cfg),
                                                  acfg))(params, opt, batch)
    check("train-step loss on mesh == dense",
          np.allclose(m_new["loss"], m_ref["loss"], rtol=1e-4))
    flat_ref = jax.tree.leaves(p_ref)
    flat_new = jax.tree.leaves(p_new)
    ok = all(np.allclose(a, b, rtol=1e-3, atol=1e-4)
             for a, b in zip(flat_ref, flat_new))
    check("train-step params on mesh == dense", ok)


def scenario_input_pipeline():
    """Domain-parallel sharded reads == sync-full batches bit-for-bit on
    1-d and 2-d meshes (horizon > 1 included), per-rank generated bytes
    shrink ∝ 1/(model-parallel ranks), and the measured per-rank bytes
    match the dataset's io_bytes_per_rank model (paper §5)."""
    from repro.configs.registry import get_config
    from repro.core.sharding import RULES_1D, RULES_2D
    from repro.data.pipeline import make_pipeline

    cfg = get_config("weathermixer-1b").reduced().replace(scheme="1d")
    bsz = 4

    def pipes(mesh, rules, mode, prefetch=0):
        return make_pipeline(cfg, mesh=mesh, rules=rules, batch_size=bsz,
                             mode=mode, prefetch=prefetch)

    # --- sharded == sync-full, bit for bit (1d mesh, horizons 1 and 3)
    mesh = make_host_mesh(model=4, data=4)
    for horizon in (1, 3):
        a = pipes(mesh, RULES_1D, "sharded").get(5, horizon)
        b = pipes(mesh, RULES_1D, "sync-full").get(5, horizon)
        for k in a:
            check(f"1d sharded == sync key={k} horizon={horizon}",
                  np.array_equal(np.asarray(a[k]), np.asarray(b[k])))

    # --- 2-d mesh (lon over mdom, channels over mtp)
    cfg2 = cfg.replace(scheme="2d")
    mesh2 = make_host_mesh(model=4, data=4, two_d=True)
    a = make_pipeline(cfg2, mesh=mesh2, rules=RULES_2D, batch_size=bsz,
                      mode="sharded", prefetch=0).get(3, 2)
    b = make_pipeline(cfg2, mesh=mesh2, rules=RULES_2D, batch_size=bsz,
                      mode="sync-full", prefetch=0).get(3, 2)
    for k in a:
        check(f"2d sharded == sync key={k}",
              np.array_equal(np.asarray(a[k]), np.asarray(b[k])))

    # --- LM token rows (per-data-rank reads)
    lcfg = get_config("internlm2-1.8b").reduced().replace(scheme="1d")
    lm = make_pipeline(lcfg, mesh=mesh, rules=RULES_1D, batch_size=8,
                       seq_len=32, mode="sharded", prefetch=0).get(1)
    lm2 = make_pipeline(lcfg, mesh=mesh, rules=RULES_1D, batch_size=8,
                        seq_len=32, mode="sync-full", prefetch=0).get(1)
    for k in lm:
        check(f"lm sharded == sync key={k}",
              np.array_equal(np.asarray(lm[k]), np.asarray(lm2[k])))

    # --- per-rank bytes ∝ 1/(model ranks), == the io model
    devs = jax.devices()
    full_bytes = 4 * bsz * cfg.wm_lat * cfg.wm_lon * cfg.wm_channels
    per_rank = {}
    for ways in (2, 4, 8):
        m = jax.make_mesh((1, ways), ("data", "model"),
                          devices=devs[:ways])
        p = pipes(m, RULES_1D, "sharded")
        p.get(0)
        ranks = p.stats.rank_bytes["fields"]
        per_rank[ways] = max(ranks.values())
        check(f"{ways}-way ranks uniform", len(set(ranks.values())) == 1)
        check(f"{ways}-way per-rank == io model",
              per_rank[ways] == p.io_bytes_per_rank(ways)
              == full_bytes // ways)
    check("per-rank bytes ∝ 1/ranks",
          per_rank[2] == 2 * per_rank[4] == 4 * per_rank[8])

    # --- prefetcher determinism: same seed => same batches as sync
    sync = pipes(mesh, RULES_1D, "sharded", prefetch=0)
    pref = pipes(mesh, RULES_1D, "sharded", prefetch=2)
    horizons = [1, 2, 1, 3]
    got = list(pref.iterate(horizons))
    want = [sync.get(i, h) for i, h in enumerate(horizons)]
    ok = all(np.array_equal(np.asarray(g[k]), np.asarray(w[k]))
             for g, w in zip(got, want) for k in g)
    check("prefetch thread == synchronous reads", ok)

    # --- per-HOST read dedup (ROADMAP follow-up): tokens are replicated
    # over the 4-way model axis, but each row group must be generated
    # once per host, not once per addressable device -- and the read
    # plan is built once, not per step.
    lp = make_pipeline(lcfg, mesh=mesh, rules=RULES_1D, batch_size=8,
                       seq_len=32, mode="sharded", prefetch=0)
    for s in range(3):
        lp.get(s)
    tok_bytes = 8 * 32 * np.dtype(np.int32).itemsize
    check("replicated tokens generated once per host per step",
          lp.stats.generated_bytes["tokens"] == 3 * tok_bytes)
    check("every model-replica rank still accounts its read",
          sum(lp.stats.rank_bytes["tokens"].values()) == 3 * 4 * tok_bytes)
    check("read plan built once per key (not per step)",
          lp.stats.plan_builds == len(lp.source.keys))


def scenario_engine_pipeline():
    """TrainEngine on a mesh: sharded+prefetch reproduces sync-full loss
    curves exactly (same seed), incl. randomized rollout; microbatch
    accumulation matches the full-batch step within fp tolerance."""
    from repro.launch.engine import EngineConfig, TrainEngine

    def run(mode, prefetch, accum=1, steps=4):
        eng = TrainEngine(
            "weathermixer-1b", mesh_model=4, mesh_data=2, scheme="1d",
            config=EngineConfig(steps=steps, batch=4, rollout=2,
                                log_every=steps - 1, pipeline=mode,
                                prefetch=prefetch, accum=accum))
        return eng.run(), eng

    h_sync, _ = run("sync-full", 0)
    h_shard, eng = run("sharded", 2)
    ok = all(np.allclose(a["loss"], b["loss"], rtol=1e-6)
             and np.allclose(a["grad_norm"], b["grad_norm"], rtol=1e-5)
             for a, b in zip(h_sync, h_shard))
    check("engine sharded+prefetch == sync-full history", ok)

    em = eng.evaluate(n_batches=1)
    check("engine eval on mesh", np.isfinite(em["val_loss"]))

    h_acc, _ = run("sharded", 2, accum=2, steps=2)
    h_one, _ = run("sharded", 2, accum=1, steps=2)
    check("accum=2 step ~= accum=1 step",
          np.allclose(h_acc[0]["loss"], h_one[0]["loss"], rtol=1e-5))


def scenario_ckpt_sharded_reshard():
    """Zero-redundancy sharded checkpointing (ISSUE 4): saving a
    jigsaw + ZeRO-1 sharded model writes only each rank's addressable
    shards (per-rank byte accounting ~= total_bytes / n_ranks, summed
    exactly to the deduplicated total -- i.e. no full-model gather
    anywhere), and restore is topology-free: the same checkpoint lands
    bit-identically under a DIFFERENT mesh (8-way ring saved, 4-way
    restored), under explicit spec overrides, and as plain numpy."""
    import tempfile

    from repro.checkpoint import sharded
    from repro.configs.registry import get_config
    from repro.launch import specs as S
    from repro.models import registry as M
    from repro.optim import adam

    cfg = get_config("weathermixer-1b").reduced().replace(scheme="1d")
    mesh = make_host_mesh(model=8, data=2)
    params = M.init(jax.random.PRNGKey(0), cfg)
    pspecs = S.sanitize_tree(
        params, S.param_specs(params, cfg, RULES_1D, mesh), mesh)
    params = jax.device_put(params, S.to_shardings(pspecs, mesh))
    opt = adam.init(params, adam.AdamConfig())
    ospecs = S.sanitize_tree(
        opt, S.opt_specs(opt["mu"], pspecs, zero1_axis="data", mesh=mesh),
        mesh)
    opt = jax.device_put(opt, S.to_shardings(ospecs, mesh))

    total = sum(l.nbytes for l in jax.tree.leaves([params, opt]))
    path = os.path.join(tempfile.mkdtemp(), "ck")
    snap = sharded.save_checkpoint(
        path, {"params": params, "opt_state": opt}, step=7,
        extra={"scheme": "1d"})
    n = len(jax.devices())
    check(f"sharded save writes total bytes exactly once "
          f"({snap.total_bytes} == {total})", snap.total_bytes == total)
    per_rank = max(snap.bytes_per_rank.values())
    check(f"per-rank bytes ~= total/n_ranks ({per_rank} vs "
          f"{total // n})", per_rank <= 2 * total // n)
    check("every rank writes something",
          len(snap.bytes_per_rank) == n
          and min(snap.bytes_per_rank.values()) > 0)

    def same(tree_a, tree_b):
        return all(np.array_equal(np.asarray(a), np.asarray(b))
                   for a, b in zip(jax.tree.leaves(tree_a),
                                   jax.tree.leaves(tree_b)))

    # restore under a DIFFERENT topology (8-way ring -> 4-way)
    mesh4 = make_host_mesh(model=4, data=2)
    got = sharded.restore_tree(path, "params", like=params, mesh=mesh4)
    check("resharded restore (8-way -> 4-way) bit-identical",
          same(got, params))
    w = got["blocks"]["ch_fc1"]["w"]
    check("restored leaves actually live on the 4-way mesh",
          dict(w.sharding.mesh.shape) == {"data": 2, "model": 4}
          and "model" in tuple(w.sharding.spec))

    # explicit spec override beats the saved spec
    got2 = sharded.restore_tree(
        path, "params", mesh=mesh4,
        specs={"blocks": {"ch_fc1": {"w": P(None, None, "model")}}})
    check("spec-override restore bit-identical", same(got2, params))

    # host-side restore (no mesh): plain numpy, still validated
    npy = sharded.restore_tree(path, "opt_state", like=opt)
    check("numpy restore bit-identical (opt_state incl. zero1 moments)",
          same(npy, opt))

    # restore on the SAME topology keeps the saved zero1 layout
    same_mesh = sharded.restore_tree(path, "opt_state", mesh=mesh)
    mu = same_mesh["mu"]["blocks"]["ch_fc1"]["w"]
    flat_axes = [a for e in mu.sharding.spec if e is not None
                 for a in (e if isinstance(e, tuple) else (e,))]
    check("same-topology restore keeps the zero1 data-axis shard",
          "data" in flat_axes)


def scenario_resume_exact():
    """Exact-resume (ISSUE 4): a run interrupted at step k and resumed
    from its sharded checkpoint reproduces the uninterrupted loss
    history BIT-FOR-BIT (params, Adam state incl. step, rollout
    schedule, and the data-pipeline cursor all restored), on a mesh,
    with ZeRO-1 moments and the async writer in the loop."""
    import tempfile

    from repro.launch.engine import EngineConfig, TrainEngine

    path = os.path.join(tempfile.mkdtemp(), "ck")

    def engine(**kw):
        return TrainEngine(
            "weathermixer-1b", mesh_model=4, mesh_data=2, scheme="1d",
            config=EngineConfig(steps=6, batch=4, rollout=2, zero1=True,
                                log_every=1, pipeline="sharded",
                                prefetch=2, **kw))

    full = engine()
    h_full = full.run()

    # "interrupted" run: async checkpoint at step 4 (loop index 3),
    # then the process goes away
    interrupted = engine(ckpt=path, ckpt_every=3)
    interrupted.run()
    check("interrupted run checkpointed mid-flight (async writer)",
          interrupted.last_save is not None
          and os.path.exists(os.path.join(path + "-3", "manifest.json")))
    per = interrupted.last_save.bytes_per_rank
    total = interrupted.last_save.total_bytes
    n_mesh = interrupted.mesh.devices.size
    check(f"engine save is sharded, not gathered (max rank "
          f"{max(per.values())} of {total})",
          max(per.values()) <= 2 * total // n_mesh)

    resumed = engine(resume=path + "-3")
    check("resume restored the step index", resumed.step_idx == 4)
    check("resume restored the pipeline cursor",
          resumed.pipeline.cursor == 4)
    h_res = resumed.run()

    tail = [h for h in h_full if h["step"] >= 4]
    check("resumed history length", len(h_res) == len(tail) == 2)
    ok = all(a["loss"] == b["loss"] and a["lr"] == b["lr"]
             and a["grad_norm"] == b["grad_norm"]
             for a, b in zip(tail, h_res))
    check("interrupted-at-k + resume == uninterrupted history "
          "(bit-for-bit)", ok)


def scenario_preempt_resume_exact():
    """Fault tolerance end-to-end (ISSUE 7): a REAL SIGTERM mid-run (the
    chaos hook self-delivers it after step 3), the child finishes the
    in-flight step, takes a final synchronous save, exits the resumable
    code; the Supervisor rediscovers the checkpoint and relaunches with
    ``--resume``; the concatenated loss history of the two child
    processes is BIT-IDENTICAL to an uninterrupted in-process run on the
    same seed."""
    import json
    import tempfile

    from repro.launch import resilience
    from repro.launch.engine import EngineConfig, TrainEngine

    steps, kill_at = 8, 3
    root = tempfile.mkdtemp()

    # uninterrupted in-process reference
    ref = TrainEngine(
        "weathermixer-1b", mesh_model=4, mesh_data=2, scheme="1d",
        config=EngineConfig(steps=steps, batch=4, rollout=2, zero1=True,
                            log_every=1))
    h_ref = ref.run()

    src = os.path.join(os.path.dirname(__file__), "..", "src")
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.abspath(src) + os.pathsep \
        + env.get("PYTHONPATH", "")
    # the chaos hook: attempt 0's loop hits i==3 and self-SIGTERMs; the
    # resumed child starts at i==4, so the SAME env never re-fires
    env[resilience.PREEMPT_ENV] = str(kill_at)

    def build(resume, attempt):
        cmd = [sys.executable, "-m", "repro.launch.train",
               "--arch", "weathermixer-1b", "--steps", str(steps),
               "--batch", "4", "--rollout", "2", "--zero1",
               "--mesh-model", "4", "--mesh-data", "2", "--scheme", "1d",
               "--log-every", "1", "--ckpt", os.path.join(root, "ck"),
               "--metrics-out", os.path.join(root, f"m{attempt}.json")]
        if resume:
            cmd += ["--resume", resume]
        return cmd

    sup = resilience.Supervisor(build, ckpt_root=root, prefix="ck",
                                max_restarts=3, env=env)
    rc = sup.run()
    check(f"supervised run finished clean (rc={rc})", rc == 0)
    check(f"attempt exit codes {sup.attempts} == "
          f"[{resilience.RESUMABLE_EXIT_CODE}, 0]",
          sup.attempts == [resilience.RESUMABLE_EXIT_CODE, 0])
    check("relaunch resumed from the preemption checkpoint",
          sup.resumes[0] is None and sup.resumes[1] is not None
          and sup.resumes[1].endswith(f"ck-{kill_at}"))
    check("resumable exit relaunched immediately (no backoff)",
          sup.backoffs == [])

    with open(os.path.join(root, "m0.json")) as f:
        h0 = [json.loads(line) for line in f if line.strip()]
    with open(os.path.join(root, "m1.json")) as f:
        h1 = [json.loads(line) for line in f if line.strip()]
    check(f"first child logged steps 0..{kill_at}",
          [h["step"] for h in h0] == list(range(kill_at + 1)))
    check(f"second child logged steps {kill_at + 1}..{steps - 1}",
          [h["step"] for h in h1] == list(range(kill_at + 1, steps)))
    h_cat = h0 + h1
    ok = all(a["loss"] == b["loss"] and a["lr"] == b["lr"]
             and a["grad_norm"] == b["grad_norm"]
             for a, b in zip(h_ref, h_cat))
    check("SIGTERM + supervisor restart == uninterrupted history "
          "(bit-for-bit)", ok)


def scenario_elastic_reshard_resume():
    """Elastic resume (ISSUE 7): a ZeRO-1 run checkpointed on an 8-device
    mesh (model=4 x data=2) resumes on a 4-device mesh (model=2 x
    data=2) -- the engine refits params AND the zero1 moment/master
    layouts to the new mesh -- with loss continuity, and a save from the
    resumed engine shards bytes across the 4 survivors.  Plus the
    pod-scale completeness contract: per-process index fragments, rank-0
    merge, and a half-written pod save that stays invisible to
    ``latest_checkpoint``."""
    import tempfile

    from repro.checkpoint import sharded
    from repro.launch.engine import EngineConfig, TrainEngine

    root = tempfile.mkdtemp()
    path = os.path.join(root, "ck")

    def engine(mesh_model, mesh_data, **kw):
        return TrainEngine(
            "weathermixer-1b", mesh_model=mesh_model, mesh_data=mesh_data,
            scheme="1d",
            config=EngineConfig(steps=6, batch=4, zero1=True,
                                log_every=1, **kw))

    # the "big" run: 8 devices, periodic save at loop index 3 (step 4)
    big = engine(4, 2, ckpt=path, ckpt_every=3)
    h_big = big.run()
    ck = f"{path}-3"
    check("8-way run checkpointed mid-flight",
          sharded.checkpoint_complete(ck))
    check("latest_checkpoint picks the final (higher-step) save",
          sharded.latest_checkpoint(root, prefix="ck") == path)

    # resume on HALF the devices
    small = engine(2, 2, resume=ck)
    check("elastic resume restored the step index", small.step_idx == 4)
    check("elastic resume restored the pipeline cursor",
          small.pipeline.cursor == 4)
    mu = small.opt_state["mu"]["blocks"]["ch_fc1"]["w"]
    check("restored zero1 moments live on the 4-device mesh",
          dict(mu.sharding.mesh.shape) == {"data": 2, "model": 2})
    flat = [a for e in mu.sharding.spec if e is not None
            for a in (e if isinstance(e, tuple) else (e,))]
    check("zero1 moment layout refit to the new mesh (data axis kept)",
          "data" in flat)

    h_small = small.run()
    tail = [h for h in h_big if h["step"] >= 4 and "eval" not in h]
    check("resumed history length", len(h_small) == len(tail) == 2)
    ok = all(np.allclose(a["loss"], b["loss"], rtol=1e-3, atol=1e-4)
             for a, b in zip(tail, h_small))
    check("8-way -> 4-way loss continuity (fp tolerance: reduction "
          "order differs across mesh extents)", ok)

    # byte accounting on the resumed topology: a fresh save spreads the
    # bytes over the 4 surviving devices
    small.save(os.path.join(root, "ck-resharded"), block=True)
    per = small.last_save.bytes_per_rank
    total = small.last_save.total_bytes
    check(f"resharded save is sharded over the survivors "
          f"(max rank {max(per.values())} of {total})",
          len(per) == 4 and max(per.values()) <= 2 * total // 4)

    # ---- pod-scale completeness: per-process indexes + rank-0 merge ----
    snap = sharded.snapshot(
        {"params": big.params, "opt_state": big.opt_state},
        step=big.step_idx, mesh=big.mesh)
    assign = {d: (0 if i < 4 else 1)
              for i, d in enumerate(sorted(snap.bytes_per_rank))}
    frags = sharded.partition_snapshot(snap, assign)
    check("partition splits the byte accounting exactly",
          sum(sum(f.bytes_per_rank.values()) for f in frags.values())
          == snap.total_bytes)

    pod = os.path.join(root, "pod")
    # process 1 lands first: shards + index fragment, NO manifest yet
    sharded.write_snapshot(frags[1], pod, process_index=1,
                           process_count=2)
    check("half-written pod save is incomplete (no manifest)",
          not sharded.checkpoint_complete(pod))
    check("half-written pod save invisible to latest_checkpoint",
          sharded.latest_checkpoint(root, prefix="pod") is None)
    # process 0 lands: writes its shards, merges, publishes the manifest
    sharded.write_snapshot(frags[0], pod, process_index=0,
                           process_count=2)
    check("finalized pod save is complete",
          sharded.checkpoint_complete(pod)
          and sharded.latest_checkpoint(root, prefix="pod") == pod)
    got = sharded.restore_tree(pod, "params")
    want = sharded.restore_tree(path, "params")
    ok = all(np.array_equal(np.asarray(a), np.asarray(b))
             for a, b in zip(jax.tree.leaves(got), jax.tree.leaves(want)))
    check("pod-save restore bit-identical to the single-process save",
          ok)


def scenario_serving_restore():
    """Serving restore (ISSUE 8): an 8-way (model=4 x data=2) sharded
    training checkpoint's params group lands on 1-, 2-, 4- and 8-way
    DATA-ONLY serving meshes; fp32 rollouts through the ForecastEngine
    are BIT-identical across every serving shape (and to the plain
    numpy single-device restore), and a bf16-policy checkpoint serves
    both natively (bf16) and cast to fp32 on restore."""
    import tempfile

    from repro.checkpoint.serving import restore_serving_params
    from repro.data.weather import WeatherDataConfig, WeatherDataset
    from repro.launch.engine import EngineConfig, TrainEngine
    from repro.models import registry as M
    from repro.serve.engine import ForecastEngine, ServeConfig

    root = tempfile.mkdtemp()
    cks = {}
    for prec in (None, "bf16"):
        tag = prec or "fp32"
        path = os.path.join(root, f"ck-{tag}")
        eng = TrainEngine("weathermixer-1b", mesh_model=4, mesh_data=2,
                          scheme="1d",
                          config=EngineConfig(steps=3, batch=4,
                                              precision=prec,
                                              log_every=10))
        eng.run()
        eng.save(path, block=True)
        cks[tag] = path

    cfg = ForecastEngine("weathermixer-1b").cfg   # reduced serving config
    ds = WeatherDataset(WeatherDataConfig(
        lat=cfg.wm_lat, lon=cfg.wm_lon, channels=cfg.wm_channels, seed=3))
    fields = ds.sample_batch(0, 5)["fields"]
    leads = [1, 2, 3, 2, 1]

    outs = {}
    for nd in (1, 2, 4, 8):
        se = ForecastEngine("weathermixer-1b", ckpt=cks["fp32"],
                            mesh_data=nd,
                            config=ServeConfig(buckets=(2, 4)))
        res = se.serve(fields, leads)
        check(f"fp32 restore on data={nd} serves every request",
              all(r.done() for r in res))
        outs[nd] = np.stack([r.result() for r in res])
    for nd in (2, 4, 8):
        check(f"fp32 rollouts bit-identical: serving data={nd} == data=1",
              np.array_equal(outs[nd], outs[1]))

    # ground truth: plain numpy restore, hand-rolled rollout, no engine
    np_params, man = restore_serving_params(cks["fp32"], arch="weathermixer-1b")
    check("manifest carries training metadata (precision, step)",
          man.extra.get("precision") in ("fp32", "legacy")
          and man.step >= 1)
    se1 = ForecastEngine("weathermixer-1b", params=np_params)
    ref = []
    for f, ld in zip(fields, leads):
        x = jnp.asarray(f[None])
        for _ in range(ld):
            x = M.forecast_step(se1.params, x, se1.cfg, se1.jcfg)
        ref.append(np.asarray(x[0]))
    # eager op-by-op vs the engine's jitted padded-batch step: XLA fuses
    # differently, so this reference is tolerance-level (the bitwise
    # guarantee above is across serving MESH SHAPES, all jitted)
    check("engine rollouts match the hand-rolled numpy restore",
          np.allclose(outs[1], np.stack(ref), rtol=1e-5, atol=1e-5))

    # bf16 checkpoint: native bf16 serving and fp32-cast serving
    outs16 = {}
    for prec in ("bf16", "fp32"):
        se = ForecastEngine("weathermixer-1b", ckpt=cks["bf16"],
                            mesh_data=4,
                            config=ServeConfig(buckets=(2, 4),
                                               precision=prec))
        w = se.params["encoder"]["w"]
        want = jnp.bfloat16 if prec == "bf16" else jnp.float32
        check(f"bf16 ckpt served at {prec}: weights are {want.__name__}",
              w.dtype == want)
        res = se.serve(fields, leads)
        outs16[prec] = np.stack([np.asarray(r.result(), np.float32)
                                 for r in res])
    check("bf16 vs fp32-cast serving of the same ckpt agree loosely",
          np.allclose(outs16["bf16"], outs16["fp32"], rtol=0.1, atol=0.1))


def scenario_telemetry_trace():
    """Unified telemetry end-to-end (ISSUE 9): an instrumented wm-1b
    training run on a 4x2 mesh produces (a) a Perfetto-loadable Chrome
    trace whose dispatch / eval / ckpt_submit spans nest inside their
    step span and whose pipeline.produce spans live on the prefetch
    thread's track with the feed's pipeline.h2d inside them, (b) a
    JSONL whose per-step records hold the measured fields and agree
    with the Chrome trace's spans, and (c) an HLO collective-byte count
    that cross-checks the analytic wire model to within a small
    factor."""
    import json
    import math
    import tempfile

    from repro import telemetry
    from repro.launch.engine import EngineConfig, TrainEngine
    from repro.launch import trace_report

    root = tempfile.mkdtemp()
    trace = os.path.join(root, "run.trace.json")
    eng = TrainEngine(
        "weathermixer-1b", mesh_model=4, mesh_data=2, scheme="1d",
        config=EngineConfig(steps=6, batch=4, log_every=2,
                            ckpt=os.path.join(root, "ck"), ckpt_every=2,
                            trace=trace))
    eng.run()

    # -- Chrome trace: schema + nesting --------------------------------
    with open(trace) as f:
        doc = json.load(f)
    evs = doc["traceEvents"]
    xs = [e for e in evs if e.get("ph") == "X"]
    names = {e["name"] for e in xs}
    check(f"trace has the span taxonomy ({sorted(names)})",
          {"data_wait", "step", "dispatch", "ckpt_submit",
           "pipeline.produce", "ckpt.write"} <= names)

    def within(child, parent):
        return (parent["ts"] <= child["ts"] and
                child["ts"] + child["dur"]
                <= parent["ts"] + parent["dur"] + 1e-3)

    steps = [e for e in xs if e["name"] == "step"]
    check("one step span per training step", len(steps) == 6)

    def enclosing_step(e):
        return any(p["tid"] == e["tid"] and within(e, p) for p in steps)

    disp = [e for e in xs if e["name"] == "dispatch"]
    check("every dispatch span nests inside a step span",
          len(disp) == 6 and all(enclosing_step(e) for e in disp))
    subs = [e for e in xs if e["name"] == "ckpt_submit"]
    check("periodic ckpt_submit spans nest inside their step span "
          "(final save is outside the loop)",
          sum(enclosing_step(e) for e in subs) >= 2)
    main_tid = steps[0]["tid"]
    prod = [e for e in xs if e["name"] == "pipeline.produce"]
    check("pipeline.produce spans run on the prefetch thread's track",
          prod and all(e["tid"] != main_tid for e in prod))
    wr = [e for e in xs if e["name"] == "ckpt.write"]
    check("ckpt.write spans run off the main thread (async writer)",
          wr and all(e["tid"] != main_tid for e in wr))
    h2d = [e for e in xs if e["name"] == "pipeline.h2d"]
    check("one pipeline.h2d span per batch, each inside a "
          "pipeline.produce span on the prefetch thread's track",
          len(h2d) == 6 and all(
              e["tid"] != main_tid and any(
                  p["tid"] == e["tid"] and within(e, p) for p in prod)
              for e in h2d))
    comp = [e for e in xs if e["name"] == "jax.compile"]
    check("backend compiles recorded as jax.compile spans on the "
          "threads that compiled", comp and all(
              e["dur"] > 0 and e["args"]["fun"] for e in comp))

    # -- JSONL: measured step records + trace_report -------------------
    jpath = telemetry.jsonl_path_for(trace)
    meta, srecs, spans, *_ = trace_report.split_records(
        trace_report.load_records(jpath))
    check("trace JSONL parses with 6 step records", len(srecs) == 6)
    check("trace-report --check passes (finite, non-negative dur_s and "
          "data_wait_s)", trace_report.check(meta, srecs) == [])
    check("step records hold the measured fields only",
          all(set(s) == {"kind", "step", "rollout", "dur_s", "data_wait_s",
                         "device"} for s in srecs))
    check("each record's data_wait_s is its data_wait span",
          all(math.isclose(s["data_wait_s"], w["dur"] / 1e6,
                           rel_tol=1e-6, abs_tol=1e-9)
              for s, w in zip(srecs, sorted(
                  (e for e in xs if e["name"] == "data_wait"),
                  key=lambda e: e["args"]["step"]))))
    check("span summary counts the new spans",
          spans["pipeline.h2d"]["count"] == 6
          and spans["jax.compile"]["count"] == len(comp))

    # -- HLO cross-check: analytic wire bytes vs compiled collectives --
    # model-only mesh (no data axis): the analytic model counts ONLY
    # jigsaw mixer traffic, so a data-axis grad all-reduce would swamp
    # the comparison
    eng1 = TrainEngine("weathermixer-1b", mesh_model=4, mesh_data=1,
                       scheme="1d",
                       config=EngineConfig(steps=1, batch=4))
    with eng1._mesh_ctx():
        batch = eng1.pipeline.get(0, 1)
        compiled = eng1.step_fns[1].lower(
            eng1.params, eng1.opt_state, batch).compile()
    measured = telemetry.hlo_collective_bytes(compiled)
    model = eng1.cost_model.comm_bytes_per_device
    ratio = measured / model
    check(f"HLO collective bytes within 4x of the analytic wire model "
          f"(measured {measured:.3g}, model {model:.3g}, "
          f"ratio {ratio:.2f})", 0.25 <= ratio <= 4.0)


SCENARIOS = {name[len("scenario_"):]: fn
             for name, fn in list(globals().items())
             if name.startswith("scenario_")}


def main():
    names = sys.argv[1:] or list(SCENARIOS)
    for n in names:
        print(f"[scenario] {n}")
        SCENARIOS[n]()
    print("ALL-OK")


if __name__ == "__main__":
    main()
