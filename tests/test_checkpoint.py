"""Sharded checkpoint subsystem (DESIGN.md §9): manifest layout,
save/restore round-trips, leaf validation with key paths, cross-shard
slice reassembly, the async writer (overlap + in-flight guard + error
propagation), pipeline cursor state, and single-device exact resume.
Multi-device save/reshard/resume runs as dist scenarios
(``ckpt_sharded_reshard`` here via subprocess; ``resume_exact`` via
test_distributed.py)."""
import json
import os
import subprocess
import sys
import threading
import time

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from repro.checkpoint import io as ckpt_io
from repro.checkpoint import manifest as MF
from repro.checkpoint import sharded
from repro.checkpoint.writer import AsyncCheckpointWriter
from repro.optim import adam

HERE = os.path.dirname(__file__)


def _params():
    return {"layer": {"w": jnp.arange(12.0).reshape(3, 4),
                      "b": jnp.zeros((4,), jnp.float32)},
            "embed": {"table": jnp.ones((4, 2))},
            "blend": jnp.arange(3, dtype=jnp.int32)}


# -- facade ------------------------------------------------------------

def test_facade_roundtrip_layout_and_meta(tmp_path):
    params = _params()
    opt = adam.init(params, adam.AdamConfig())
    path = str(tmp_path / "ck")
    ckpt_io.save(path, params, opt, step=42, extra={"arch": "t"})
    # layout: manifest + one shard file for the single rank
    assert os.path.exists(os.path.join(path, "manifest.json"))
    man = ckpt_io.load_manifest(path)
    assert man.step == 42 and man.extra["arch"] == "t"
    assert set(man.groups) == {"params", "opt_state"}
    p2, o2, step = ckpt_io.restore(path, like_params=params, like_opt=opt)
    assert step == 42 and int(o2["step"]) == 0
    for a, b in zip(jax.tree.leaves(p2), jax.tree.leaves(params)):
        np.testing.assert_array_equal(a, np.asarray(b))
        assert a.dtype == np.asarray(b).dtype   # int32 leaf survives


def test_bfloat16_roundtrip(tmp_path):
    """Regression: npz stores bf16 as raw void ('|V2'); restore must
    reinterpret against the manifest dtype, not hand back garbage.
    Production configs default to param_dtype='bfloat16', so this is
    the dtype real-run checkpoints actually use."""
    params = {"w": jnp.arange(12.0, dtype=jnp.bfloat16).reshape(3, 4),
              "s": jnp.float32(2.0)}
    path = str(tmp_path / "ck")
    ckpt_io.save(path, params, step=7)
    p2, _, step = ckpt_io.restore(path, like_params=params)
    assert step == 7
    assert p2["w"].dtype == jnp.bfloat16
    np.testing.assert_array_equal(np.asarray(p2["w"], np.float32),
                                  np.asarray(params["w"], np.float32))


def test_bfloat16_cross_shard_reassembly(tmp_path):
    """bf16 must also survive the slow path (slice assembly from
    multiple shard files, not the exact-match member fast path)."""
    full = np.asarray(jnp.arange(16, dtype=jnp.bfloat16).reshape(4, 4))
    shards = (MF.ShardEntry("shard-d00000.npz", "params/w#0",
                            ((0, 2), (0, 4)), 0),
              MF.ShardEntry("shard-d00001.npz", "params/w#0",
                            ((2, 4), (0, 4)), 1))
    entry = MF.LeafEntry((4, 4), "bfloat16", [None, None], shards)
    man = MF.Manifest(step=0, groups={"params": {"w": entry}})
    blobs = {"shard-d00000.npz": {"params/w#0": full[:2]},
             "shard-d00001.npz": {"params/w#0": full[2:]}}
    path = str(tmp_path / "ck")
    sharded.write_snapshot(sharded.Snapshot(man, blobs, {}), path)
    rd = sharded._ShardReader(path)
    got = rd.read(entry, ((1, 3), (0, 4)))       # crosses the boundary
    assert got.dtype == np.dtype("bfloat16")
    np.testing.assert_array_equal(got.astype(np.float32),
                                  full[1:3].astype(np.float32))


def test_snapshot_copies_host_numpy_leaves(tmp_path):
    """The snapshot must capture values at submit time even for plain
    numpy leaves the caller mutates in place afterwards."""
    arr = np.arange(6.0)
    snap = sharded.snapshot({"params": {"x": arr}}, step=0)
    arr *= 100.0
    path = str(tmp_path / "ck")
    sharded.write_snapshot(snap, path)
    got, _, _ = ckpt_io.restore(path)
    np.testing.assert_array_equal(got["x"], np.arange(6.0))


def test_restore_validates_shape_with_keypath(tmp_path):
    path = str(tmp_path / "ck")
    ckpt_io.save(path, _params(), step=1)
    bad = _params()
    bad["layer"]["w"] = jnp.zeros((3, 5))
    with pytest.raises(ValueError, match=r"params\[/layer/w\].*shape"):
        ckpt_io.restore(path, like_params=bad)


def test_restore_validates_dtype_with_keypath(tmp_path):
    """Regression (ISSUE 4 satellite): dtype mismatches used to pass
    silently through restore."""
    path = str(tmp_path / "ck")
    ckpt_io.save(path, _params(), step=1)
    bad = _params()
    bad["blend"] = bad["blend"].astype(jnp.float32)
    with pytest.raises(ValueError, match=r"params\[/blend\].*dtype"):
        ckpt_io.restore(path, like_params=bad)


def test_restore_key_mismatch_lists_paths(tmp_path):
    path = str(tmp_path / "ck")
    ckpt_io.save(path, _params(), step=1)
    with pytest.raises(ValueError, match="key mismatch"):
        ckpt_io.restore(path, like_params={"w": jnp.zeros((3, 3))})


def test_restore_missing_manifest(tmp_path):
    with pytest.raises(FileNotFoundError, match="manifest"):
        ckpt_io.restore(str(tmp_path / "nope"))


# -- manifest ----------------------------------------------------------

def test_spec_serde_roundtrip():
    from jax.sharding import PartitionSpec as P
    for spec in [P(), P(None, "model"), P(("data", "model"), None),
                 P("data", None, "model")]:
        assert MF.spec_from_json(MF.spec_to_json(spec)) == spec


def test_manifest_rejects_foreign_format(tmp_path):
    import json
    path = str(tmp_path / "ck")
    os.makedirs(path)
    with open(os.path.join(path, "manifest.json"), "w") as f:
        json.dump({"format": "not-a-ckpt"}, f)
    with pytest.raises(ValueError, match="format"):
        ckpt_io.load_manifest(path)


# -- cross-shard reassembly (the resharding kernel of restore) ---------

def _two_shard_checkpoint(path):
    """Hand-built checkpoint: leaf (4, 4) saved as two row shards, the
    layout an e.g. 2-way mesh would have written."""
    full = np.arange(16, dtype=np.float32).reshape(4, 4)
    shards = (MF.ShardEntry("shard-d00000.npz", "params/w#0",
                            ((0, 2), (0, 4)), 0),
              MF.ShardEntry("shard-d00001.npz", "params/w#0",
                            ((2, 4), (0, 4)), 1))
    entry = MF.LeafEntry((4, 4), "float32", [None, None], shards)
    man = MF.Manifest(step=0, groups={"params": {"w": entry}})
    blobs = {"shard-d00000.npz": {"params/w#0": full[:2]},
             "shard-d00001.npz": {"params/w#0": full[2:]}}
    sharded.write_snapshot(sharded.Snapshot(man, blobs, {}), path)
    return full, entry


def test_reader_reassembles_cross_shard_slices(tmp_path):
    path = str(tmp_path / "ck")
    full, entry = _two_shard_checkpoint(path)
    rd = sharded._ShardReader(path)
    # a slice crossing the shard boundary (what a resharded mesh asks for)
    got = rd.read(entry, ((1, 3), (1, 4)))
    np.testing.assert_array_equal(got, full[1:3, 1:4])
    # exact shard fast path and full read
    np.testing.assert_array_equal(rd.read(entry, ((0, 2), (0, 4))),
                                  full[:2])
    np.testing.assert_array_equal(rd.read(entry, ((0, 4), (0, 4))), full)


def test_reader_detects_coverage_holes(tmp_path):
    path = str(tmp_path / "ck")
    _, entry = _two_shard_checkpoint(path)
    holey = MF.LeafEntry(entry.shape, entry.dtype, entry.spec,
                         entry.shards[:1])     # second shard "lost"
    rd = sharded._ShardReader(path)
    with pytest.raises(ValueError, match="cover"):
        rd.read(holey, ((0, 4), (0, 4)))


def test_reader_overlapping_shards_dont_mask_holes(tmp_path):
    """Coverage is a boolean mask, not a volume sum: two shards that
    overlap each other but leave a hole must still raise, not return
    np.empty garbage in the hole."""
    full = np.arange(16, dtype=np.float32).reshape(4, 4)
    shards = (MF.ShardEntry("shard-d00000.npz", "params/w#0",
                            ((0, 2), (0, 4)), 0),
              MF.ShardEntry("shard-d00000.npz", "params/w#1",
                            ((0, 2), (0, 4)), 0))   # duplicate block
    entry = MF.LeafEntry((4, 4), "float32", [None, None], shards)
    man = MF.Manifest(step=0, groups={"params": {"w": entry}})
    blobs = {"shard-d00000.npz": {"params/w#0": full[:2],
                                  "params/w#1": full[:2]}}
    path = str(tmp_path / "ck")
    sharded.write_snapshot(sharded.Snapshot(man, blobs, {}), path)
    rd = sharded._ShardReader(path)
    with pytest.raises(ValueError, match="cover"):
        rd.read(entry, ((0, 4), (0, 4)))   # rows 2:4 uncovered


def test_reader_missing_shard_file(tmp_path):
    path = str(tmp_path / "ck")
    _, entry = _two_shard_checkpoint(path)
    os.remove(os.path.join(path, "shard-d00001.npz"))
    rd = sharded._ShardReader(path)
    with pytest.raises(FileNotFoundError, match="shard"):
        rd.read(entry, ((0, 4), (0, 4)))


# -- async writer ------------------------------------------------------

class _SlowWriter:
    """Instrumented write_fn: records concurrency and completion, and
    holds the write open until released."""

    def __init__(self, delay=0.0):
        self.delay = delay
        self.active = 0
        self.max_active = 0
        self.done = []
        self._lock = threading.Lock()

    def __call__(self, snap, path):
        with self._lock:
            self.active += 1
            self.max_active = max(self.max_active, self.active)
        time.sleep(self.delay)
        sharded.write_snapshot(snap, path)
        with self._lock:
            self.active -= 1
            self.done.append(path)


def test_async_writer_overlaps_and_snapshots(tmp_path):
    """The save must (a) return while the write is still in flight --
    the caller can keep training -- and (b) capture the values at
    submit time, immune to later in-place updates."""
    slow = _SlowWriter(delay=1.0)
    w = AsyncCheckpointWriter(write_fn=slow)
    params = {"w": jnp.arange(8.0)}
    path = str(tmp_path / "ck")
    w.save(path, {"params": params}, step=3)
    assert w.in_flight                       # returned before the write
    # "one train step" of work completes while the write is in flight
    params = {"w": params["w"] * 2.0}
    jax.block_until_ready(params["w"])
    assert w.in_flight
    w.wait()
    assert not w.in_flight and slow.done == [path]
    got, _, step = ckpt_io.restore(path)
    assert step == 3
    np.testing.assert_array_equal(got["w"], np.arange(8.0))  # pre-mutation


def test_async_writer_in_flight_guard(tmp_path):
    """At most one write in flight: a second save waits for the first,
    and both land completely."""
    slow = _SlowWriter(delay=0.2)
    w = AsyncCheckpointWriter(write_fn=slow)
    p1, p2 = str(tmp_path / "a"), str(tmp_path / "b")
    w.save(p1, {"params": {"x": jnp.zeros(4)}}, step=1)
    w.save(p2, {"params": {"x": jnp.ones(4)}}, step=2)   # guard: waits
    w.wait()
    assert slow.max_active == 1
    assert slow.done == [p1, p2]
    assert ckpt_io.restore(p1)[2] == 1 and ckpt_io.restore(p2)[2] == 2


def test_async_writer_raises_write_errors_at_wait(tmp_path):
    def boom(snap, path):
        raise IOError("disk full")
    w = AsyncCheckpointWriter(write_fn=boom)
    w.save(str(tmp_path / "ck"), {"params": {"x": jnp.zeros(2)}})
    with pytest.raises(IOError, match="disk full"):
        w.wait()
    w.wait()                                  # error consumed; reusable


# -- pipeline cursor ---------------------------------------------------

def test_pipeline_cursor_tracks_and_restores():
    from repro.configs.registry import get_config
    from repro.data.pipeline import make_pipeline
    cfg = get_config("weathermixer-1b").reduced()
    pipe = make_pipeline(cfg, batch_size=2, prefetch=0)
    list(pipe.iterate([1, 1, 1]))
    assert pipe.state() == {"cursor": 3}
    # a fresh pipeline restored to cursor=3 continues the same stream
    fresh = make_pipeline(cfg, batch_size=2, prefetch=0)
    fresh.set_state({"cursor": 3})
    nxt = next(iter(fresh.iterate([2])))
    want = pipe.get(3, 2)
    for k in want:
        np.testing.assert_array_equal(np.asarray(nxt[k]),
                                      np.asarray(want[k]))


# -- engine exact resume (single device) -------------------------------

def test_engine_exact_resume(tmp_path):
    from repro.launch.engine import EngineConfig, TrainEngine
    path = str(tmp_path / "ck")

    def engine(**kw):
        return TrainEngine("internlm2-1.8b",
                           config=EngineConfig(steps=4, batch=2,
                                               seq_len=16, log_every=1,
                                               rollout=2, **kw))

    h_full = engine().run()
    engine(ckpt=path, ckpt_every=2).run()     # checkpoints at step 3
    resumed = engine(resume=path + "-2")
    assert resumed.step_idx == 3
    h_res = resumed.run()
    tail = [h for h in h_full if h["step"] >= 3]
    assert len(h_res) == len(tail) == 1
    assert h_res[0]["loss"] == tail[0]["loss"]
    assert h_res[0]["lr"] == tail[0]["lr"]
    assert h_res[0]["grad_norm"] == tail[0]["grad_norm"]


def test_engine_resume_rejects_schedule_mismatch(tmp_path):
    from repro.launch.engine import EngineConfig, TrainEngine
    path = str(tmp_path / "ck")
    TrainEngine("internlm2-1.8b",
                config=EngineConfig(steps=2, batch=2, seq_len=16,
                                    log_every=1, seed=0, ckpt=path)).run()
    with pytest.raises(ValueError, match="seed"):
        TrainEngine("internlm2-1.8b",
                    config=EngineConfig(steps=2, batch=2, seq_len=16,
                                        log_every=1, seed=1, resume=path))


# -- keep-last-k GC + best marker (ISSUE 5 satellite) ------------------

def test_keep_last_k_ckpt_gc(tmp_path):
    """EngineConfig(keep_ckpts=2): only the newest 2 periodic checkpoint
    dirs survive; the final (non-periodic) checkpoint is never GC'd."""
    from repro.launch.engine import EngineConfig, TrainEngine
    path = str(tmp_path / "ck")
    eng = TrainEngine("weathermixer-1b", config=EngineConfig(
        steps=7, batch=2, log_every=10, ckpt=path, ckpt_every=1,
        keep_ckpts=2, async_save=False))
    eng.run()
    eng.wait_checkpoints()
    have = sorted(p.name for p in tmp_path.iterdir())
    # periodic saves land at ck-1..ck-6; only the last two survive
    assert "ck-5" in have and "ck-6" in have
    assert not any(f"ck-{i}" in have for i in range(1, 5)), have
    assert "ck" in have                      # final save untouched
    # survivors are complete, restorable checkpoints
    from repro import checkpoint as ckpt
    assert ckpt.load_manifest(str(tmp_path / "ck-6")).step == 7


def test_ckpt_gc_spares_best_marker_target(tmp_path):
    """The best-eval marker's checkpoint is exempt from GC."""
    from repro.launch.engine import EngineConfig, TrainEngine
    path = str(tmp_path / "ck")
    eng = TrainEngine("weathermixer-1b", config=EngineConfig(
        steps=8, batch=2, log_every=10, ckpt=path, ckpt_every=2,
        keep_ckpts=1, eval_every=3, eval_batches=1, async_save=False))
    eng.run()
    eng.wait_checkpoints()
    assert eng.best_ckpt is not None
    assert os.path.exists(eng.best_ckpt), (eng.best_ckpt,
                                           sorted(os.listdir(tmp_path)))
    marker = json.load(open(path + "-best.json"))
    assert marker["path"] == eng.best_ckpt
    assert marker["val_loss"] == pytest.approx(eng.best_val)


def test_writer_prunes_only_after_write(tmp_path):
    """AsyncCheckpointWriter.save(prune=...) deletes the old dirs only
    once the new checkpoint is durable (manifest present)."""
    old = tmp_path / "old"
    old.mkdir()
    (old / "x").write_text("stale")
    seen = {}

    def slow_write(snap, path):
        seen["old_alive_during_write"] = old.exists()
        sharded.write_snapshot(snap, path)

    w = AsyncCheckpointWriter(write_fn=slow_write)
    w.save(str(tmp_path / "new"), {"g": {"a": np.arange(4)}},
           prune=[str(old)])
    w.wait()
    assert seen["old_alive_during_write"]    # not pruned before
    assert not old.exists()                  # pruned after
    assert os.path.exists(tmp_path / "new" / "manifest.json")


# -- crash-safe shard writes (ISSUE 7 satellite) -----------------------

def test_shard_writes_are_atomic(tmp_path, monkeypatch):
    """A process killed mid-npz-write must never leave a truncated shard
    at the final name: the payload goes to ``.tmp`` and is renamed into
    place.  Simulated by making the rename step fail."""
    params = {"w": np.arange(8.0)}
    path = str(tmp_path / "ck")

    real_replace = os.replace

    def no_replace(src, dst):
        raise OSError("killed before rename")

    monkeypatch.setattr(os, "replace", no_replace)
    with pytest.raises(OSError, match="killed"):
        sharded.save_checkpoint(path, {"params": params})
    monkeypatch.setattr(os, "replace", real_replace)
    names = sorted(os.listdir(path))
    # only tmp debris, nothing at a final name -> directory reads as torn
    assert all(n.endswith(".tmp") for n in names), names
    assert not sharded.checkpoint_complete(path)

    # a clean write leaves no tmp files behind
    sharded.save_checkpoint(path, {"params": params})
    names = sorted(os.listdir(path))
    assert not any(n.endswith(".tmp") for n in names), names
    assert sharded.checkpoint_complete(path)


def test_manifest_written_last(tmp_path):
    """Ordering contract: every shard file a manifest references exists
    by the time the manifest does (write_snapshot streams shards first)."""
    order = []
    real = sharded._write_npz_atomic

    def spy(fname, members):
        order.append(os.path.basename(fname))
        real(fname, members)

    path = str(tmp_path / "ck")
    snap = sharded.snapshot({"params": {"w": np.arange(4.0)}})
    try:
        sharded._write_npz_atomic = spy
        sharded.write_snapshot(snap, path)
    finally:
        sharded._write_npz_atomic = real
    assert order == ["shard-d00000.npz"]     # shards before manifest.save


# -- writer retry-with-backoff (ISSUE 7 satellite) ---------------------

def test_writer_retries_transient_oserror(tmp_path):
    calls = []

    def flaky(snap, path):
        calls.append(path)
        if len(calls) < 3:
            raise OSError("EIO: nfs blip")
        sharded.write_snapshot(snap, path)

    w = AsyncCheckpointWriter(write_fn=flaky, retry_backoff=0.01)
    path = str(tmp_path / "ck")
    w.save(path, {"params": {"x": np.arange(4.0)}}, step=9)
    w.wait()                                  # no error: 3rd attempt won
    assert len(calls) == 3
    assert ckpt_io.restore(path)[2] == 9


def test_writer_retry_budget_exhausted(tmp_path):
    calls = []

    def always_fails(snap, path):
        calls.append(path)
        raise OSError("disk gone")

    w = AsyncCheckpointWriter(write_fn=always_fails, retries=3,
                              retry_backoff=0.01)
    w.save(str(tmp_path / "ck"), {"params": {"x": np.arange(2.0)}})
    with pytest.raises(OSError, match="disk gone"):
        w.wait()
    assert len(calls) == 3                    # exactly the retry budget


def test_writer_does_not_retry_nontransient_errors(tmp_path):
    calls = []

    def type_bug(snap, path):
        calls.append(path)
        raise ValueError("not weather, a bug")

    w = AsyncCheckpointWriter(write_fn=type_bug, retry_backoff=0.01)
    w.save(str(tmp_path / "ck"), {"params": {"x": np.arange(2.0)}})
    with pytest.raises(ValueError):
        w.wait()
    assert len(calls) == 1


# -- latest_checkpoint discovery (ISSUE 7 satellite) -------------------

def _mini_ckpt(path, step):
    sharded.save_checkpoint(str(path), {"params": {"w": np.arange(4.0)}},
                            step=step)


def test_latest_checkpoint_picks_newest_complete(tmp_path):
    assert sharded.latest_checkpoint(str(tmp_path)) is None  # cold start
    _mini_ckpt(tmp_path / "ck-2", 2)
    _mini_ckpt(tmp_path / "ck-5", 5)
    assert sharded.latest_checkpoint(str(tmp_path)) == \
        str(tmp_path / "ck-5")
    # by manifest STEP, not directory name ordering
    _mini_ckpt(tmp_path / "ck-10", 3)
    assert sharded.latest_checkpoint(str(tmp_path)) == \
        str(tmp_path / "ck-5")


def test_latest_checkpoint_skips_torn_saves(tmp_path):
    _mini_ckpt(tmp_path / "ck-1", 1)
    # torn save A: shards but no manifest (killed before the last write)
    torn = tmp_path / "ck-7"
    torn.mkdir()
    (torn / "shard-d00000.npz").write_bytes(b"partial")
    assert sharded.latest_checkpoint(str(tmp_path)) == \
        str(tmp_path / "ck-1")
    # torn save B: manifest references a shard file that is gone
    _mini_ckpt(tmp_path / "ck-9", 9)
    os.remove(tmp_path / "ck-9" / "shard-d00000.npz")
    assert not sharded.checkpoint_complete(str(tmp_path / "ck-9"))
    assert sharded.latest_checkpoint(str(tmp_path)) == \
        str(tmp_path / "ck-1")
    # torn save C: orphaned per-process index fragments, no manifest
    pod = tmp_path / "ck-11"
    pod.mkdir()
    man = MF.Manifest(step=11, groups={})
    man.save_index(str(pod), 1, 2)
    assert sharded.latest_checkpoint(str(tmp_path)) == \
        str(tmp_path / "ck-1")
    # ...and none of them crash restore discovery or complete-checks
    assert not sharded.checkpoint_complete(str(torn))
    assert not sharded.checkpoint_complete(str(pod))


def test_latest_checkpoint_prefix_filter(tmp_path):
    _mini_ckpt(tmp_path / "ck-3", 3)
    _mini_ckpt(tmp_path / "other-8", 8)
    _mini_ckpt(tmp_path / "ckextra", 9)      # not ck or ck-*: excluded
    assert sharded.latest_checkpoint(str(tmp_path), prefix="ck") == \
        str(tmp_path / "ck-3")
    assert sharded.latest_checkpoint(str(tmp_path), prefix="other") == \
        str(tmp_path / "other-8")
    # root itself can be the checkpoint
    _mini_ckpt(tmp_path / "solo", 1)
    assert sharded.latest_checkpoint(str(tmp_path / "solo")) == \
        str(tmp_path / "solo")


def test_latest_checkpoint_after_engine_gc(tmp_path):
    """Discovery composes with keep-last-k GC + the best marker: what
    the engine leaves behind is exactly what latest_checkpoint ranks,
    and the GC'd dirs are gone, not candidates."""
    from repro.launch.engine import EngineConfig, TrainEngine
    path = str(tmp_path / "ck")
    eng = TrainEngine("weathermixer-1b", config=EngineConfig(
        steps=7, batch=2, log_every=10, ckpt=path, ckpt_every=1,
        keep_ckpts=2, async_save=False))
    eng.run()
    eng.wait_checkpoints()
    # final save (step 7) outranks the surviving periodic ck-5/ck-6
    assert sharded.latest_checkpoint(str(tmp_path), prefix="ck") == path
    # drop the final save: the newest surviving periodic wins
    import shutil
    shutil.rmtree(path)
    assert sharded.latest_checkpoint(str(tmp_path), prefix="ck") == \
        path + "-6"


# -- per-process index merge (pod-scale completeness) ------------------

def _fragment(step, fname, rows, full):
    shard = MF.ShardEntry(fname, "params/w#0", (rows, (0, 4)), 0)
    entry = MF.LeafEntry((4, 4), "float32", [None, None], (shard,))
    man = MF.Manifest(step=step, groups={"params": {"w": entry}})
    return sharded.Snapshot(man, {fname: {"params/w#0":
                                          full[rows[0]:rows[1]]}}, {})


def test_pod_save_merges_index_fragments(tmp_path):
    full = np.arange(16, dtype=np.float32).reshape(4, 4)
    path = str(tmp_path / "ck")
    f0 = _fragment(4, "shard-d00000.npz", (0, 2), full)
    f1 = _fragment(4, "shard-d00001.npz", (2, 4), full)
    # process 1 first: index fragment lands, manifest does not
    sharded.write_snapshot(f1, path, process_index=1, process_count=2)
    assert os.path.exists(os.path.join(path, MF.index_name(1)))
    assert not os.path.exists(os.path.join(path, MF.MANIFEST_NAME))
    assert not sharded.checkpoint_complete(path)
    # process 0: writes, waits for all fragments, merges, finalizes
    sharded.write_snapshot(f0, path, process_index=0, process_count=2)
    assert sharded.checkpoint_complete(path)
    man = ckpt_io.load_manifest(path)
    assert man.step == 4
    assert len(man.groups["params"]["w"].shards) == 2
    got = sharded.restore_tree(path, "params")
    np.testing.assert_array_equal(got["w"], full)


def test_pod_finalize_times_out_on_missing_rank(tmp_path):
    full = np.arange(16, dtype=np.float32).reshape(4, 4)
    path = str(tmp_path / "ck")
    f0 = _fragment(2, "shard-d00000.npz", (0, 2), full)
    os.makedirs(path)
    f0.manifest.save_index(path, 0, 3)
    with pytest.raises(TimeoutError, match="index-p00001"):
        sharded.finalize_checkpoint(path, 3, timeout=0.2, poll=0.02)
    assert not os.path.exists(os.path.join(path, MF.MANIFEST_NAME))


def test_merge_manifests_rejects_torn_pod_save(tmp_path):
    full = np.arange(16, dtype=np.float32).reshape(4, 4)
    f0 = _fragment(2, "shard-d00000.npz", (0, 2), full)
    f1 = _fragment(3, "shard-d00001.npz", (2, 4), full)   # step skew
    with pytest.raises(ValueError, match="torn pod save"):
        MF.merge_manifests([f0.manifest, f1.manifest])


# -- GC prune backlog survives failed/final saves (ISSUE 7 satellite) --

def test_final_save_survives_stale_write_error_and_prunes(tmp_path):
    """A failed async periodic write must not (a) abort the NEXT save --
    in production that next save is the final preemption save -- or (b)
    orphan its GC prune list.  The engine absorbs the stale error at
    save(), re-queues the backlog, re-raises at wait_checkpoints()."""
    from repro.launch.engine import EngineConfig, TrainEngine
    path = str(tmp_path / "ck")
    eng = TrainEngine("weathermixer-1b", config=EngineConfig(
        steps=4, batch=2, log_every=10, ckpt=path, ckpt_every=1,
        keep_ckpts=1))                        # async writer in the loop
    # third periodic write (ck-3) fails after the engine has queued
    # ck-1/ck-2 deletions behind it
    real = sharded.write_snapshot
    calls = []

    def flaky(snap, p, **kw):
        calls.append(p)
        if len(calls) == 3:
            raise OSError("transient EIO")
        return real(snap, p, **kw)

    eng._writer._write_fn = flaky
    eng._writer.retries = 1                   # no writer-level retry
    # the loop must NOT abort mid-run; the absorbed error re-surfaces at
    # run()'s own wait_checkpoints() barrier -- AFTER the final save
    with pytest.raises(OSError, match="EIO"):
        eng.run()
    eng.wait_checkpoints()                    # error consumed exactly once
    # the final save landed despite the stale error...
    assert sharded.checkpoint_complete(path)
    # ...and the prune backlog was drained by it: older periodic dirs
    # are gone (keep_ckpts=1)
    survivors = {n for n in os.listdir(tmp_path)
                 if n.startswith("ck-")
                 and sharded.checkpoint_complete(str(tmp_path / n))}
    assert "ck-1" not in survivors and "ck-2" not in survivors, survivors
    assert sharded.latest_checkpoint(str(tmp_path), prefix="ck") == path


def test_prune_backlog_persisted_and_restored(tmp_path):
    """The backlog rides in manifest extra: a run that dies before its
    deletions execute hands them to the resumed engine."""
    from repro.launch.engine import EngineConfig, TrainEngine
    stale = tmp_path / "ck-0"
    stale.mkdir()
    path = str(tmp_path / "ck")
    eng = TrainEngine("internlm2-1.8b", config=EngineConfig(
        steps=2, batch=2, seq_len=16, log_every=1, ckpt=path,
        async_save=False))
    eng._prune_backlog = [str(stale)]
    eng.run()
    man = ckpt_io.load_manifest(path)
    # the final save drained the backlog (dir deleted) and recorded it
    assert not stale.exists()
    assert man.extra["prune_backlog"] == [str(stale)]
    # a resumed engine drops already-deleted entries
    res = TrainEngine("internlm2-1.8b", config=EngineConfig(
        steps=2, batch=2, seq_len=16, log_every=1, resume=path))
    assert res._prune_backlog == []


# -- multi-device: sharded save + resharded restore --------------------

def test_ckpt_sharded_reshard_scenario():
    """16 emulated devices in a subprocess: per-rank byte accounting
    (no full-model gather) + save-on-8-way / restore-on-4-way."""
    env = dict(os.environ)
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=16"
    env["JAX_PLATFORMS"] = "cpu"
    res = subprocess.run(
        [sys.executable, os.path.join(HERE, "dist_scenarios.py"),
         "ckpt_sharded_reshard"],
        env=env, capture_output=True, text=True, timeout=600)
    assert res.returncode == 0 and "ALL-OK" in res.stdout, (
        f"\nstdout:\n{res.stdout[-3000:]}\nstderr:\n{res.stderr[-3000:]}")
