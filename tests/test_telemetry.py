"""Telemetry subsystem tests (DESIGN.md §14): tracer semantics (span
nesting/ordering under threads, explicit-time spans, Chrome trace-event
schema, counter and gauge behavior under contention), the spans' place
in a ``jax.profiler`` trace and the backend-compile spans, exporter
flush on preemption, and the analytic step-cost counts pinned against
the Fig. 7 roofline numbers for weathermixer-1b."""
import json
import math
import os
import subprocess
import sys
import threading

import pytest

from repro import telemetry
from repro.configs.registry import get_config
from repro.launch import analysis as A
from repro.launch import trace_report
from repro.telemetry.spans import Tracer


# ---------------------------------------------------------------------------
# spans
# ---------------------------------------------------------------------------

def _xs(tr, name=None):
    evs = [e for e in tr.chrome_events() if e.get("ph") == "X"]
    return [e for e in evs if name is None or e["name"] == name]


def test_span_nesting_single_thread():
    tr = Tracer()
    with tr.span("outer", step=0):
        with tr.span("inner_a"):
            pass
        with tr.span("inner_b"):
            pass
    outer, = _xs(tr, "outer")
    for inner in _xs(tr, "inner_a") + _xs(tr, "inner_b"):
        assert outer["ts"] <= inner["ts"]
        assert inner["ts"] + inner["dur"] <= outer["ts"] + outer["dur"]
        assert inner["tid"] == outer["tid"]
    a, = _xs(tr, "inner_a")
    b, = _xs(tr, "inner_b")
    assert a["ts"] + a["dur"] <= b["ts"]          # sequenced, not nested
    assert outer["args"] == {"step": 0}


def test_span_dur_s_readable_after_exit():
    tr = Tracer()
    with tr.span("work") as sp:
        pass
    assert sp.dur_s >= 0.0 and sp.dur_ns >= 0


def test_span_tracks_per_thread():
    tr = Tracer()
    barrier = threading.Barrier(3)

    def worker(tag):
        barrier.wait()
        for i in range(5):
            with tr.span("w", tag=tag, i=i):
                with tr.span("w.child", tag=tag):
                    pass

    ts = [threading.Thread(target=worker, args=(k,), name=f"th-{k}")
          for k in range(2)]
    for t in ts:
        t.start()
    barrier.wait()
    with tr.span("main"):
        pass
    for t in ts:
        t.join()

    spans = _xs(tr, "w")
    tids = {e["tid"] for e in spans}
    assert len(spans) == 10 and len(tids) == 2
    # every child is contained in a parent ON ITS OWN TRACK
    for ch in _xs(tr, "w.child"):
        assert any(p["tid"] == ch["tid"] and p["ts"] <= ch["ts"]
                   and ch["ts"] + ch["dur"] <= p["ts"] + p["dur"]
                   for p in spans)
    # thread-name metadata covers every track
    meta = {e["tid"]: e["args"]["name"]
            for e in tr.chrome_events() if e.get("ph") == "M"
            and e["name"] == "thread_name"}
    for tid in tids:
        assert meta[tid].startswith("th-")


def test_disabled_tracer_records_no_events_but_counts():
    tr = Tracer(enabled=False)
    with tr.span("invisible") as sp:
        pass
    assert sp.dur_s == 0.0                 # the shared null span
    tr.event("also_invisible")
    assert _xs(tr) == []
    assert tr.counter("c", 2) == 2.0       # counters stay live
    tr.gauge("g", 7)
    tr.observe("h", 0.5)
    assert tr.counters()["c"] == 2.0
    assert tr.gauges()["g"] == 7
    assert tr.hist_summary("h")["count"] == 1


def test_ring_buffer_bounds_events():
    tr = Tracer(ring=10)
    for i in range(50):
        with tr.span("s", i=i):
            pass
    spans = _xs(tr, "s")
    assert len(spans) == 10
    assert [e["args"]["i"] for e in spans] == list(range(40, 50))


def test_explicit_span_lands_in_ring_and_both_exports(tmp_path):
    tr = Tracer()
    t0 = tr.now_ns()
    t1 = t0 + 2_500_000                      # 2.5 ms later
    other, started, done = [], threading.Event(), threading.Event()

    def elsewhere():
        other.append(threading.get_ident())
        started.set()
        done.wait(10)

    th = threading.Thread(target=elsewhere, name="submitter")
    th.start()
    assert started.wait(10)
    with tr.span("outer"):
        tr.complete("explicit", t0, t1, rid=7)            # this thread
    tr.complete("queued", t0, t1, tid=other[0], rid=8)    # another track
    done.set()
    th.join(timeout=10)
    assert not th.is_alive()
    with tr.lock:
        ring = list(tr._events)
    ev = {e[1]: e for e in ring}
    # the same tuple form as span(): (ph, name, ts, dur, tid, depth, args)
    assert ev["explicit"] == ("X", "explicit", t0 - tr.t0_ns, 2_500_000,
                              threading.get_ident(), 1, {"rid": 7})
    assert ev["queued"][4:] == (other[0], 0, {"rid": 8})
    xs = {e["name"]: e for e in _xs(tr)}
    assert xs["explicit"]["dur"] == pytest.approx(2500.0)
    assert xs["explicit"]["args"] == {"rid": 7}
    meta = {e["tid"]: e["args"]["name"] for e in tr.chrome_events()
            if e.get("ph") == "M" and e["name"] == "thread_name"}
    assert meta[other[0]] == "submitter"
    path = str(tmp_path / "out.trace.json")
    tr.export_chrome(path)
    with open(path) as f:
        names = {e["name"] for e in json.load(f)["traceEvents"]}
    assert {"explicit", "queued"} <= names
    tr.export_jsonl(telemetry.jsonl_path_for(path))
    _, _, spans, *_ = trace_report.split_records(trace_report.load_records(
        telemetry.jsonl_path_for(path)))
    assert spans["explicit"] == {"count": 1, "total_s": 0.0025,
                                 "mean_s": 0.0025}
    assert spans["queued"]["count"] == 1


class _Annotations:
    """Stand-in annotation factory: records the names it is entered
    with."""

    def __init__(self):
        self.entered = []

    def __call__(self, name):
        import contextlib
        self.entered.append(name)
        return contextlib.nullcontext()


def test_span_enters_the_annotation_of_its_name():
    ann = _Annotations()
    tr = Tracer()
    tr.annotate = ann
    with tr.span("outer", step=0):
        with tr.span("inner"):
            pass
    assert ann.entered == ["outer", "inner"]
    assert {e["name"] for e in _xs(tr)} == {"outer", "inner"}


def test_disabled_tracer_records_neither_span_nor_annotation():
    ann = _Annotations()
    tr = Tracer(enabled=False)
    tr.annotate = ann
    with tr.span("invisible"):
        pass
    t = tr.now_ns()
    tr.complete("also_invisible", t, t + 1)
    assert ann.entered == [] and _xs(tr) == []


def _host_plane_names(trace_dir):
    import glob
    from jax.profiler import ProfileData
    path, = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                      recursive=True)
    names = set()
    for plane in ProfileData.from_file(path).planes:
        if plane.name.startswith("/host:"):
            for line in plane.lines:
                names.update(ev.name for ev in line.events)
    return names


def _profiled(trace_dir, fn):
    import jax
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(str(trace_dir), profiler_options=opts)
    try:
        fn()
    finally:
        jax.profiler.stop_trace()
    return _host_plane_names(str(trace_dir))


def test_spans_show_on_the_profiler_host_plane(tmp_path):
    """An attached tracer's spans are annotations in a jax.profiler
    trace, read back by name from its host plane; a disabled one's are
    not."""
    on = telemetry.attach_profiler(Tracer())
    off = telemetry.attach_profiler(Tracer(enabled=False))

    def work():
        with on.span("repro.test.on"):
            with on.span("repro.test.on.inner"):
                pass
        with off.span("repro.test.off"):
            pass

    names = _profiled(tmp_path / "prof", work)
    assert {"repro.test.on", "repro.test.on.inner"} <= names
    assert "repro.test.off" not in names


def test_train_engine_spans_in_a_profiler_trace(tmp_path):
    """A short CPU run of TrainEngine under jax.profiler: the loop's and
    the feed's spans sit by name on the trace's host plane."""
    from repro.launch.engine import EngineConfig, TrainEngine
    eng = TrainEngine("internlm2-1.8b", config=EngineConfig(
        steps=2, batch=2, seq_len=16, log_every=1 << 30))
    names = _profiled(tmp_path / "prof", eng.run)
    assert {"data_wait", "step", "dispatch", "pipeline.produce",
            "pipeline.h2d"} <= names


def test_fresh_jit_compile_records_one_compile_span():
    import jax
    import numpy as np
    tr = telemetry.attach_profiler(Tracer())
    telemetry.attach_profiler(tr)               # attaching twice: one sink
    fn = jax.jit(lambda x: x * 3.0 - 1.0)
    t0 = tr.now_ns()
    with tr.span("caller"):
        fn(np.ones(11, np.float32))
    t1 = tr.now_ns()
    fn(np.ones(11, np.float32))                 # cached: no compile
    comp = _xs(tr, "jax.compile")
    assert len(comp) == 1
    c, = comp
    caller, = _xs(tr, "caller")
    assert c["tid"] == caller["tid"] == threading.get_ident()
    assert (t0 - tr.t0_ns) / 1e3 <= c["ts"] + 1.0
    assert c["ts"] + c["dur"] <= (t1 - tr.t0_ns) / 1e3
    assert c["dur"] > 0 and "lambda" in c["args"]["fun"]


# ---------------------------------------------------------------------------
# Chrome trace schema
# ---------------------------------------------------------------------------

def test_chrome_export_schema(tmp_path):
    tr = Tracer()
    with tr.span("step", step=0):
        with tr.span("dispatch"):
            pass
    tr.event("preempt.signal", signum=15)
    tr.gauge("pipeline.queue_depth", 2)
    path = str(tmp_path / "out.trace.json")
    tr.export_chrome(path)
    with open(path) as f:
        doc = json.load(f)
    assert set(doc) == {"traceEvents", "displayTimeUnit"}
    evs = doc["traceEvents"]
    assert all({"name", "ph", "pid", "tid"} <= set(e) for e in evs)
    phases = {e["ph"] for e in evs}
    assert {"M", "X", "i", "C"} <= phases
    for e in evs:
        if e["ph"] == "X":
            assert isinstance(e["ts"], float) and isinstance(e["dur"],
                                                             float)
            assert e["ts"] >= 0 and e["dur"] >= 0
        if e["ph"] == "i":
            assert e["s"] == "t"
        if e["ph"] == "C":
            assert e["args"]           # the plotted value
    names = {e["name"] for e in evs}
    assert {"process_name", "thread_name", "step", "dispatch",
            "preempt.signal", "pipeline.queue_depth"} <= names


def test_jsonl_export_roundtrip(tmp_path):
    tr = Tracer()
    tr.set_meta(arch="x", mesh_model=2)
    tr.step_record(step=0, rollout=1, dur_s=0.5, data_wait_s=0.1,
                   device="cpu")
    with tr.span("step", step=0):
        pass
    tr.counter("c")
    tr.observe("h", 1.0)
    path = str(tmp_path / "out.trace.jsonl")
    tr.export_jsonl(path)
    with open(path) as f:
        recs = [json.loads(line) for line in f]
    kinds = [r["kind"] for r in recs]
    assert kinds[0] == "meta" and recs[0]["arch"] == "x"
    assert {"step", "spans", "counters", "gauges",
            "histogram"} <= set(kinds)
    meta, steps, spans, counters, _, hists = \
        trace_report.split_records(recs)
    assert meta["mesh_model"] == 2 and len(steps) == 1
    assert spans["step"]["count"] == 1
    assert counters["c"] == 1 and hists[0]["name"] == "h"
    assert trace_report.check(meta, steps) == []


# ---------------------------------------------------------------------------
# counters / gauges / histograms
# ---------------------------------------------------------------------------

def test_counter_monotonic_and_exact_under_threads():
    tr = Tracer(enabled=False)
    per, threads = 500, 8

    def worker():
        prev = -1.0
        for _ in range(per):
            v = tr.counter("hits")
            assert v > prev              # monotonic as observed here
            prev = v
        tr.add_counters({"bytes": 10, "batches": 1})

    ts = [threading.Thread(target=worker) for _ in range(threads)]
    for t in ts:
        t.start()
    for t in ts:
        t.join()
    got = tr.counters()
    assert got["hits"] == per * threads   # no lost read-modify-writes
    assert got["bytes"] == 10 * threads
    assert got["batches"] == threads


def test_gauge_is_last_value():
    tr = Tracer()
    for v in (3, 1, 7):
        tr.gauge("depth", v)
    assert tr.gauges()["depth"] == 7
    # each update is also a plotted Chrome "C" sample
    cs = [e for e in tr.chrome_events() if e.get("ph") == "C"]
    assert [e["args"]["value"] for e in cs] == [3, 1, 7]


def test_histogram_percentiles():
    tr = Tracer()
    for v in range(1, 101):
        tr.observe("lat", float(v))
    s = tr.hist_summary("lat")
    assert s["count"] == 100 and s["min"] == 1 and s["max"] == 100
    assert s["p50"] == 51 and s["p99"] == 100
    assert tr.percentile("lat", 0.95) == 96
    assert math.isnan(tr.percentile("nope", 0.5))
    assert tr.hist_summary("nope") == {"count": 0}


def test_pipeline_stats_batch_is_atomic():
    """The satellite fix: PipelineStats updates ride the tracer lock as
    one critical section per batch -- hammer it from threads and the
    totals are exact."""
    from repro.data.pipeline import PipelineStats
    st = PipelineStats()
    n, per = 6, 200

    def worker(k):
        for i in range(per):
            st.record_batch([("fields", k, 100, True),
                             ("fields", 1000 + k, 100, False)], steps=1)

    ts = [threading.Thread(target=worker, args=(k,)) for k in range(n)]
    for t in ts:
        t.start()
    for t in ts:
        t.join()
    assert st.steps == n * per
    assert st.generated_bytes["fields"] == 100 * n * per
    assert sum(st.rank_bytes["fields"].values()) == 2 * 100 * n * per


# ---------------------------------------------------------------------------
# MFU / comm-fraction accounting, pinned against Fig. 7
# ---------------------------------------------------------------------------

WM = "weathermixer-1b"


def test_fig7_point_pinned():
    """``fig7_point``'s wm-1b rows of the Fig. 7 roofline; these
    constants are PINNED -- if they move, the roofline model changed."""
    cfg = get_config(WM)
    p1 = telemetry.fig7_point(cfg, 1)
    assert p1["peak_frac"] == pytest.approx(1.0)
    assert p1["t_coll_s"] == 0.0
    p2 = telemetry.fig7_point(cfg, 2)
    assert p2["peak_frac"] == pytest.approx(0.785543, rel=1e-4)
    assert p2["tflops_per_dev"] == pytest.approx(154.752, rel=1e-4)
    assert p2["regime"] == "compute-comm"
    p4 = telemetry.fig7_point(cfg, 4)
    assert p4["peak_frac"] == pytest.approx(0.646827, rel=1e-4)
    # chunked overlap hides the 2-way ring entirely behind compute
    pc = telemetry.fig7_point(cfg, 2, impl="ring_chunked")
    assert pc["peak_frac"] == pytest.approx(1.0)
    # scaling sanity: wider jigsaw -> smaller per-device step time
    assert p4["t_step_s"] < p2["t_step_s"] < p1["t_step_s"]


class _Dev:
    """Stand-in for a jax Device: only platform and device_kind matter."""

    def __init__(self, platform, device_kind):
        self.platform, self.device_kind = platform, device_kind


def test_peaks_table_keyed_by_device_kind():
    cfg = get_config(WM)
    v5e = telemetry.build_cost_model(cfg, device=_Dev("tpu", "TPU v5 lite"))
    assert v5e.device == "TPU v5 lite"
    assert v5e.peak_flops == A.PEAKS["TPU v5 lite"].flops_bf16 == 197e12
    assert A.PEAKS["TPU v5 lite"].hbm_bw == 819e9
    assert v5e.t_compute_s == pytest.approx(
        v5e.flops_per_step / 197e12)
    # an unknown TPU kind never borrows v5e's peaks
    with pytest.raises(ValueError, match="TPU v9 imaginary"):
        telemetry.build_cost_model(cfg, device=_Dev("tpu", "TPU v9 imaginary"))
    # a CPU run names its device and carries no MFU
    cpu = telemetry.build_cost_model(cfg, device=_Dev("cpu", "cpu"))
    assert cpu.device == "cpu" and cpu.peak_flops is None
    assert cpu.t_compute_s is None
    assert cpu.flops_per_step == v5e.flops_per_step > 0


def test_engine_step_records_name_their_device():
    from repro.launch.engine import EngineConfig, TrainEngine
    import jax
    eng = TrainEngine("weathermixer-1b", config=EngineConfig(
        steps=1, batch=1, log_every=1))
    eng.run()
    rec, = eng.tracer.step_records()
    assert rec["device"] == jax.devices()[0].device_kind
    # measured fields only: nothing derived from the analytic model
    assert set(rec) == {"step", "rollout", "dur_s", "data_wait_s",
                        "device"}
    assert rec["dur_s"] > 0 and rec["data_wait_s"] >= 0
    meta, steps, *_ = trace_report.split_records(
        [dict(eng.tracer._meta, kind="meta"), dict(rec, kind="step")])
    assert trace_report.check(meta, steps) == []


def test_cost_model_mfu_8way():
    """wm-1b on an 8-way model mesh: the analytic counts stamped into
    the run's meta header, pinned."""
    cfg = get_config(WM)
    cm = telemetry.build_cost_model(cfg, n_model=8, n_data=1, batch=1)
    assert cm.n_devices == 8
    assert cm.flops_per_step == 49_019_107_737_600.0
    assert cm.comm_bytes_per_device == 2_228_990_400.0
    assert cm.hops == 7 and cm.bytes_per_hop == 17_690_400.0
    assert cm.wire_dtype_bytes == 2
    assert cm.t_compute_s == pytest.approx(0.031103494757360406, rel=1e-12)
    assert cm.t_compute_s == pytest.approx(
        cm.flops_per_step / 8 / A.PEAK_FLOPS_BF16, rel=1e-12)
    assert cm.t_collective_s == pytest.approx(0.044579808, rel=1e-12)
    assert cm.t_collective_s == pytest.approx(
        cm.comm_bytes_per_device / A.ICI_BW, rel=1e-12)
    meta = cm.as_meta()
    assert meta["t_compute_s"] == cm.t_compute_s
    assert meta["t_collective_s"] == cm.t_collective_s
    assert meta["n_devices"] == 8


def test_cost_model_comm_matches_fig7_collective_term():
    """The cost model's per-device collective seconds at batch=1 equal
    the Fig. 7 t_coll for the same (config, way) -- same formula, same
    constants, independently arrived at."""
    cfg = get_config(WM).replace(scheme="1d")
    cm = telemetry.build_cost_model(cfg, n_model=2, n_data=1, batch=1)
    p2 = telemetry.fig7_point(cfg, 2)
    assert cm.t_collective_s == pytest.approx(p2["t_coll_s"], rel=1e-12)


def test_cost_model_meta_roundtrips_through_report(tmp_path):
    """The meta header and the measured step fields survive the JSONL
    export and ``trace_report``'s reading, check and rendering."""
    cfg = get_config(WM).reduced()
    cm = telemetry.build_cost_model(cfg, n_model=4, n_data=2, batch=8)
    tr = Tracer()
    tr.set_meta(arch=WM, cost_model=cm.as_meta())
    recs = [dict(step=i, rollout=1, dur_s=0.01 * (i + 1),
                 data_wait_s=0.001 * i, device="cpu") for i in range(3)]
    for r in recs:
        tr.step_record(**r)
        with tr.span("step", step=r["step"]):
            pass
    path = str(tmp_path / "run.trace.jsonl")
    tr.export_jsonl(path)
    meta, steps, spans, *_ = trace_report.split_records(
        trace_report.load_records(path))
    assert meta["cost_model"] == json.loads(json.dumps(cm.as_meta()))
    assert [{k: v for k, v in s.items() if k != "kind"}
            for s in steps] == recs
    assert spans["step"]["count"] == 3
    assert trace_report.check(meta, steps) == []
    import io
    buf = io.StringIO()
    trace_report.render(path, out=buf)
    text = buf.getvalue()
    assert "-- steps (3, on cpu) --" in text
    for name in ("dur_s", "data_wait_s", "step"):
        assert name in text
    assert trace_report.main([path, "--check"]) == 0


def test_trace_report_check_catches_bad_records():
    assert trace_report.check({}, []) == [
        "no meta header record", "no step records"]
    bad = [{"step": 0, "dur_s": float("nan"), "data_wait_s": 0.0},
           {"step": 1, "dur_s": 0.1, "data_wait_s": float("inf")}]
    fails = trace_report.check({"arch": "x"}, bad)
    assert any("step 0: dur_s" in f and "not finite" in f for f in fails)
    assert any("step 1: data_wait_s" in f and "not finite" in f
               for f in fails)
    bad2 = [{"step": 2, "dur_s": -0.1, "data_wait_s": 0.0},
            {"step": 3, "dur_s": 0.1, "data_wait_s": -1e-3},
            {"step": 4, "dur_s": 0.1}]
    fails = trace_report.check({"arch": "x"}, bad2)
    assert any("step 2: dur_s" in f and "negative" in f for f in fails)
    assert any("step 3: data_wait_s" in f and "negative" in f
               for f in fails)
    assert "step 4: missing data_wait_s" in fails


# ---------------------------------------------------------------------------
# engine integration: exporter flush on Preempted
# ---------------------------------------------------------------------------

def test_trace_flushed_on_preempted(tmp_path):
    """A preempted run must leave a complete, loadable trace behind --
    the moment the operator most needs it."""
    from repro.launch import resilience
    from repro.launch.engine import EngineConfig, TrainEngine

    trace = str(tmp_path / "run.trace.json")
    eng = TrainEngine(
        "internlm2-1.8b",
        config=EngineConfig(steps=4, batch=2, seq_len=16, log_every=1,
                            ckpt=str(tmp_path / "ck"), trace=trace,
                            preempt_at_step=1))
    with pytest.raises(resilience.Preempted):
        eng.run()

    with open(trace) as f:
        doc = json.load(f)
    names = {e["name"] for e in doc["traceEvents"]}
    assert {"step", "dispatch", "data_wait", "preempt.chaos_sigterm",
            "preempt.signal", "preempt.final_save"} <= names

    meta, steps, *_ = trace_report.split_records(
        trace_report.load_records(telemetry.jsonl_path_for(trace)))
    assert [s["step"] for s in steps] == [0, 1]   # flushed through i=1
    assert trace_report.check(meta, steps) == []
    assert meta["arch"] == "internlm2-1.8b"
    assert meta["cost_model"]["flops_per_step"] > 0


def test_metrics_json_compat_mode(tmp_path):
    """--metrics-format json keeps the legacy whole-file dump (written
    once, at run end -- not O(n^2) re-dumped every flush)."""
    from repro.launch.engine import EngineConfig, TrainEngine

    mfile = str(tmp_path / "m.json")
    eng = TrainEngine(
        "internlm2-1.8b",
        config=EngineConfig(steps=3, batch=2, seq_len=16, log_every=1,
                            metrics_out=mfile, metrics_format="json",
                            telemetry=False))
    hist = eng.run()
    with open(mfile) as f:
        logged = json.load(f)                      # one JSON document
    assert [h["step"] for h in logged] == [h["step"] for h in hist]

    with pytest.raises(ValueError):
        TrainEngine("internlm2-1.8b",
                    config=EngineConfig(steps=1, metrics_format="csv"))


def test_serve_engine_latency_histograms():
    """ForecastEngine.summary percentiles come from its telemetry
    histograms, per lead time."""
    from repro.serve.engine import ForecastEngine, ServeConfig

    eng = ForecastEngine(WM, config=ServeConfig(buckets=(2,)))
    import numpy as np
    fields = np.zeros(eng.field_shape, np.float32)
    rs = [eng.submit(fields, lead) for lead in (1, 2, 2)]
    eng.drain()
    assert all(r.done() for r in rs)
    s = eng.summary(rs)
    assert s["deliveries"] == 3
    assert math.isfinite(s["p50_s"]) and math.isfinite(s["p99_s"])
    assert set(s["lead_latency_s"]) == {1, 2}
    assert s["lead_latency_s"][2]["count"] == 2
    # longer leads take more rollout steps -> no smaller latency
    assert s["lead_latency_s"][2]["p50"] >= \
        s["lead_latency_s"][1]["p50"] - 1e-9
    names = {e["name"] for e in eng.tracer.chrome_events()}
    assert {"serve.step", "serve.peel"} <= names


def test_telemetry_trace_scenario():
    """The end-to-end acceptance run (subprocess, 16 emulated devices):
    an instrumented 4x2 wm-1b training run produces a Perfetto-valid
    Chrome trace with nested data-wait/step/ckpt spans and the feed's
    pipeline.h2d spans, a JSONL of measured step records, and an HLO
    collective-byte cross-check of the wire model."""
    here = os.path.dirname(__file__)
    env = dict(os.environ)
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=16"
    env["JAX_PLATFORMS"] = "cpu"
    res = subprocess.run(
        [sys.executable, os.path.join(here, "dist_scenarios.py"),
         "telemetry_trace"],
        env=env, capture_output=True, text=True, timeout=900)
    assert res.returncode == 0 and "ALL-OK" in res.stdout, (
        f"\nstdout:\n{res.stdout[-3000:]}\nstderr:\n{res.stderr[-3000:]}")
