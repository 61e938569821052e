"""What every run does alike: the clock since process start, the look for
the chips, the compile cache, the memory peak and the result line."""
from __future__ import annotations

import gc
import json
import os
import sys
import time
from typing import Dict, List, Tuple

from harness.spec import ROOT

CACHE_DIR = os.path.join(ROOT, ".jax_cache")


def process_start() -> float:
    """``time.time()`` at which this process started (Linux /proc)."""
    try:
        with open("/proc/self/stat") as f:
            start_ticks = int(f.read().rsplit(")", 1)[1].split()[19])
        with open("/proc/uptime") as f:
            uptime = float(f.read().split()[0])
        hz = os.sysconf("SC_CLK_TCK")
        return time.time() - (uptime - start_ticks / hz)
    except (OSError, ValueError, IndexError):
        return time.time()


def enable_compile_cache() -> None:
    """JAX's persistent compilation cache, at a fixed directory of the
    checkout, for every program however quick to compile."""
    import jax
    jax.config.update("jax_compilation_cache_dir", CACHE_DIR)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)


def devices(chips: int, allow_cpu: bool = False):
    """The cell's chips; exits without a result where they are missing."""
    import jax
    devs = jax.devices()
    if devs[0].platform == "cpu" and not allow_cpu:
        sys.exit(f"bench: JAX found no accelerator (platform "
                 f"{devs[0].platform!r}); no result")
    if len(devs) < chips:
        sys.exit(f"bench: the cell needs {chips} chips, JAX found "
                 f"{len(devs)}; no result")
    return devs[:chips]


def device_info(devs, memory_peak_bytes: int) -> Dict:
    d = devs[0]
    return {"platform": d.platform, "kind": d.device_kind,
            "count": len(devs), "memory_peak_bytes": memory_peak_bytes}


def memory_peak(devs) -> int:
    """Peak bytes in use on the fullest of the chips."""
    peaks = []
    for d in devs:
        stats = d.memory_stats() or {}
        peaks.append(int(stats.get("peak_bytes_in_use", 0)))
    return max(peaks) if peaks else 0


def bytes_in_use(devs) -> int:
    """Bytes still held on the fullest of the chips."""
    return max(int((d.memory_stats() or {}).get("bytes_in_use", 0))
               for d in devs)


def free_device_memory() -> None:
    import jax
    gc.collect()
    jax.clear_caches()
    gc.collect()


def percentile(values: List[float], q: float) -> float:
    """The q-quantile (0..1) by linear interpolation between ranks."""
    v = sorted(values)
    if not v:
        return float("nan")
    pos = q * (len(v) - 1)
    lo = int(pos)
    hi = min(lo + 1, len(v) - 1)
    return v[lo] + (v[hi] - v[lo]) * (pos - lo)


def emit(result: Dict, checks: List[Tuple[str, float, float]]) -> None:
    """Each compared number beside its limit as the last lines of standard
    error, then the result as the last line of standard output."""
    for name, value, limit in checks:
        ok = "ok" if value <= limit else "FAILS"
        print(f"check {name} = {value!r} limit {limit!r} {ok}",
              file=sys.stderr, flush=True)
    result = dict(result)
    result["checks"] = {n: {"value": v, "limit": lim}
                        for n, v, lim in checks}
    print(json.dumps(result), flush=True)


def metric(value: float, unit: str) -> Dict:
    return {"value": value, "unit": unit}


def log(msg: str) -> None:
    print(f"[bench] {msg}", file=sys.stderr, flush=True)


class Profile:
    """JAX's profiler around the window (``--trace 1``), at a fixed
    directory of the checkout, with a mark at the window's start that
    ties the program's ``perf_counter`` clock to the trace's."""

    def __init__(self, on: bool):
        self.on = on
        self.dir = os.path.join(ROOT, ".bench_trace")
        self.path = None

    def start(self) -> None:
        if not self.on:
            return
        import shutil
        import jax
        shutil.rmtree(self.dir, ignore_errors=True)
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        opts.host_tracer_level = 2
        jax.profiler.start_trace(self.dir, profiler_options=opts)

    def sync_mark(self) -> None:
        """Mark the window's start (``perf_counter`` just read) in the
        trace."""
        if not self.on:
            return
        import jax
        with jax.profiler.TraceAnnotation("bench.clock_sync"):
            pass

    def stop(self) -> None:
        if not self.on:
            return
        import glob
        import jax
        jax.profiler.stop_trace()
        found = sorted(glob.glob(os.path.join(self.dir, "**", "*.xplane.pb"),
                                 recursive=True))
        self.path = found[-1] if found else None


def spans_in(tracer, t0: float, t1: float,
             main_thread: int) -> List[Tuple]:
    """The program's spans that overlap the window [t0, t1] (perf_counter
    seconds), clipped to it: (name, start from t0, duration, main), where
    ``main`` says the span ran on the thread ``main_thread`` that calls
    into the program in the window."""
    base = tracer.t0_ns / 1e9
    out = []
    with tracer.lock:
        events = list(tracer._events)
    for ph, name, ts_ns, dur_ns, tid, _depth, _args in events:
        if ph != "X":
            continue
        s = base + ts_ns / 1e9
        e = s + dur_ns / 1e9
        s, e = max(s, t0), min(e, t1)
        if e > s:
            out.append((name, s - t0, e - s, tid == main_thread))
    return out


class RunRecord:
    """What one traced run hands the per-layer metric readers."""

    def __init__(self, *, res: Dict, devs, window_s: float, spans,
                 counts: Dict, profile: "Profile"):
        from harness import peaks
        self.res = res
        self.config = res["config"]
        self.cell = res["cell"]
        self.chips = len(devs)
        self.peak = peaks.peaks_for(devs[0].device_kind)
        self.window_s = window_s
        self.spans = spans
        self.counts = counts
        self.profile = profile
        self.trace = None

    def span_total(self, *names: str) -> float:
        return sum(sp[2] for sp in self.spans if sp[0] in names)


def per_layer(run: RunRecord) -> Dict:
    """Reduce the trace, read each per-layer metric the cell reports, and
    return the result's ``metrics``, ``device`` timing and ``breakdown``."""
    from harness import spec, xtrace
    if run.profile.path is None:
        raise SystemExit("bench: the profiler wrote no trace")
    run.trace = xtrace.reduce(run.profile.path, run.window_s, run.chips,
                              run.spans)
    metrics = {}
    for m in run.res["metrics"]["per_layer"]:
        value = spec.metric_reader(m["name"], run.res["root"])(run)
        if value is not None:
            metrics[m["name"]] = metric(value, m["unit"])
    return {"metrics": metrics,
            "timing": {"busy_s": run.trace.busy_s,
                       "window_s": run.trace.window_s},
            "breakdown": run.trace.breakdown()}
