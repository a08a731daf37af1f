"""Work-packet streaming (PerfParams.stream_work_packets).

A task's io packet never materializes whole: chunk plans drive an
incremental decoder session (video.automata.StreamSession — repeated
non-reset decode_run_pts_stream calls that write into the chunk's own
array) through a bounded loader->evaluator queue, with kernel state
carried across chunk boundaries.  Reference
analog: the element cache + feeder threads
(evaluate_worker.h:207-218, decoder_automata.cpp).
"""

import os
import struct
import subprocess
import sys
import tempfile
import threading
import weakref
from types import SimpleNamespace

import numpy as np
import pytest

from scanner_tpu import (CacheMode, Client, FrameType, Kernel, NamedStream,
                         NamedVideoStream, PerfParams, register_op)
from scanner_tpu import video as scv
from scanner_tpu.storage import metadata as md
from scanner_tpu.util.metrics import registry
from scanner_tpu.common import ScannerException
from scanner_tpu.video.automata import (DecodeRun, DecoderAutomata,
                                        StreamSession)

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


CLIPS = {
    "plain": dict(num_frames=90, keyint=12),
    "bframe": dict(num_frames=90, keyint=12, bframes=2),
    "ogop": dict(num_frames=90, keyint=12, bframes=2, open_gop=True),
    "vfr": dict(num_frames=60, keyint=12, bframes=2,
                frame_pts=np.cumsum(
                    np.random.RandomState(1).randint(1, 4, 60)
                ).tolist()),
}


def _clip(db, tmp_path, case):
    """Ingest the clip of `case` (closed GOP, reordered B frames, open
    GOP, VFR); returns a maker of fresh automata over it and the row
    sets to ask of each: all rows, a random gather, rows around a GOP
    edge."""
    kw = CLIPS[case]
    p = str(tmp_path / f"{case}.mp4")
    scv.synthesize_video(p, width=64, height=48, **kw)
    _, failed = scv.ingest_videos(db, [(case, p)])
    assert not failed
    desc = db.table_descriptor(case)
    vd = scv.load_video_meta(db, case)
    n = kw["num_frames"]
    path = md.column_item_path(desc.id, "frame", 0)
    rng = np.random.RandomState(7)
    row_sets = (list(range(n)),
                sorted(rng.choice(n, 20, replace=False).tolist()),
                [0, 11, 12, 13, n - 1])

    def automata(output_format="rgb24"):
        return DecoderAutomata(db.backend, vd, path,
                               output_format=output_format)
    return automata, row_sets


@pytest.mark.parametrize("case", list(CLIPS))
def test_stream_frames_matches_get_frames(tmp_db, tmp_path, case):
    """The incremental decode session is frame-exact vs the one-shot
    path on every stream shape (closed GOP, reordered B frames,
    open GOP, VFR) and on random gathers."""
    automata, row_sets = _clip(tmp_db, tmp_path, case)
    for rows in row_sets:
        a = automata()
        ref = a.get_frames(rows)
        a.close()
        a = automata()
        got = {}
        for rr, fr in a.stream_frames(rows, packets_per_call=7):
            assert fr.base is None, "a yield owns its memory"
            for r, f in zip(rr.tolist(), fr):
                assert r not in got, "duplicate yield"
                got[r] = f
        a.close()
        assert sorted(got) == sorted(set(rows))
        for i, r in enumerate(rows):
            assert (got[r] == ref[i]).all(), (case, r)


@pytest.mark.parametrize("fmt", ["rgb24", "yuv420"])
@pytest.mark.parametrize("k", [1, 5, 16])
@pytest.mark.parametrize("case", list(CLIPS))
def test_session_decodes_into_the_callers_slices(tmp_db, tmp_path, case,
                                                 k, fmt):
    """The destination-driven session (StreamSession.decode_into) on the
    same stream shapes: slices of k rows of the caller's own buffer (one
    row; five, ending mid-GOP; sixteen, across GOPs) come out byte for
    byte as get_frames has them, and no byte outside the rows a call
    delivered is written: a guard row on either side of the buffer, the
    slots still to come, and the end of a last slice longer than what
    is left."""
    automata, row_sets = _clip(tmp_db, tmp_path, case)
    for rows in row_sets:
        a = automata(fmt)
        ref = a.get_frames(rows)
        a.close()
        a = automata(fmt)
        session = StreamSession(a, rows, packets_per_call=7)
        buf = np.full((1 + len(rows) + k,) + a.frame_shape, 0xA5, np.uint8)
        shadow = buf.copy()
        lo, delivered = 1, []
        while session.remaining:
            left = session.remaining
            got = session.decode_into(buf[lo:lo + k])
            assert len(got) == min(k, left) == left - session.remaining
            shadow[lo:lo + len(got)] = buf[lo:lo + len(got)]
            assert np.array_equal(buf, shadow), (case, lo)
            delivered += got.tolist()
            lo += len(got)
        a.close()
        assert delivered == rows
        assert np.array_equal(buf[1:1 + len(rows)], ref)


def test_session_says_which_rows_came_late(tmp_db, tmp_path):
    """A run that starts at a keyframe which cannot give its first rows
    (a false keyframe; an open-GOP head) delivers them late, from one
    keyframe earlier: the rows returned name what each slot holds, and
    every frame is get_frames' own."""
    automata, _ = _clip(tmp_db, tmp_path, "bframe")
    rows = list(range(20, 34))
    a = automata()
    ref = a.get_frames(rows)
    a.close()
    a = automata()
    run, = a.index.plan(rows)
    late_kf = int(a.index.kf_decs[np.searchsorted(a.index.kf_disps, 24)])
    assert late_kf > run.start_dec
    a.index.plan = lambda _rows: [DecodeRun(late_kf, run.end_dec,
                                            run.out_disp)]
    session = StreamSession(a, rows, packets_per_call=7)
    out = np.empty((len(rows),) + a.frame_shape, np.uint8)
    got = session.decode_into(out[:9]).tolist() \
        + session.decode_into(out[9:]).tolist()
    a.close()
    assert got == list(range(24, 34)) + [20, 21, 22, 23]
    assert session.remaining == 0
    assert np.array_equal(out, ref[[rows.index(r) for r in got]])


@pytest.mark.parametrize("bad", ["dtype", "row_bytes", "strided", "past_end"])
def test_session_refuses_a_destination_it_cannot_fill(tmp_db, tmp_path,
                                                      bad):
    automata, _ = _clip(tmp_db, tmp_path, "plain")
    a = automata()
    session = StreamSession(a, [0, 1, 2, 3])
    shape = (4,) + a.frame_shape
    out = {"dtype": lambda: np.empty(shape, np.int8),
           "row_bytes": lambda: np.empty((4, a.frame_bytes - 1), np.uint8),
           "strided": lambda: np.empty((8,) + a.frame_shape, np.uint8)[::2],
           "past_end": lambda: np.empty(shape, np.uint8)}[bad]()
    if bad == "past_end":
        session.decode_into(out)
        assert session.remaining == 0
    with pytest.raises(ScannerException):
        session.decode_into(out)
    a.close()


@register_op(name="StreamTracker", unbounded_state=True)
class StreamTracker(Kernel):
    total_rows = [0]

    def __init__(self, config):
        super().__init__(config)
        self.reset()

    def reset(self):
        self.x = 0

    def execute(self, ignore: FrameType) -> bytes:
        StreamTracker.total_rows[0] += 1
        v = self.x
        self.x += 1
        return struct.pack("=q", v)


@pytest.mark.parametrize("affinity,expected_rows", [(False, 96), (True, 64)])
def test_chunked_state_carry(tmp_path, affinity, expected_rows):
    """Chunk plans inside one task carry unbounded state chunk-to-chunk.

    Without affinity: chunk 0 of each task recomputes the task prefix
    (rows 0..start), later chunks carry — 2 tasks x 4 chunks over 64
    rows consume 32 + 64 = 96 rows (vs 2*(8+16+24+32)=160 + prefixes
    unchunked).  With affinity the inter-task chain stacks on the
    intra-task carry: every row consumed exactly once (64) — state
    flows across every chunk AND task boundary."""
    vid = str(tmp_path / "v.mp4")
    scv.synthesize_video(vid, num_frames=64, width=64, height=48, fps=24,
                         keyint=8)
    sc = Client(db_path=str(tmp_path / "db"), num_load_workers=1)
    try:
        sc.ingest_videos([("t", vid)])
        StreamTracker.total_rows[0] = 0
        frame = sc.io.Input([NamedVideoStream(sc, "t")])
        out = NamedStream(sc, "o")
        jid = sc.run(sc.io.Output(sc.ops.StreamTracker(ignore=frame),
                                  [out]),
                     PerfParams.manual(
                         8, 32, stateful_task_affinity=affinity),
                     cache_mode=CacheMode.Overwrite, show_progress=False)
        vals = [struct.unpack("=q", b)[0] for b in out.load()]
        assert vals == list(range(64))
        assert StreamTracker.total_rows[0] == expected_rows, \
            StreamTracker.total_rows[0]
        stats = sc.get_profile(jid).statistics()
        assert stats["_counters"]["stream_chunks"] == 8
    finally:
        sc.stop()


def test_chunking_off_when_disabled(tmp_path):
    vid = str(tmp_path / "v.mp4")
    scv.synthesize_video(vid, num_frames=32, width=64, height=48, fps=24)
    sc = Client(db_path=str(tmp_path / "db"))
    try:
        sc.ingest_videos([("t", vid)])
        import scanner_tpu.kernels  # noqa: F401
        frame = sc.io.Input([NamedVideoStream(sc, "t")])
        out = NamedStream(sc, "o")
        jid = sc.run(sc.io.Output(sc.ops.Histogram(frame=frame), [out]),
                     PerfParams.manual(8, 32, stream_work_packets=False),
                     cache_mode=CacheMode.Overwrite, show_progress=False)
        stats = sc.get_profile(jid).statistics()
        assert "stream_chunks" not in stats.get("_counters", {})
        assert len(list(out.load())) == 32
    finally:
        sc.stop()


ASSEMBLED = "scanner_tpu_load_assembled_rows_total"


def _count(series, **labels):
    return sum(x["value"] for x in
               registry().snapshot().get(series, {"samples": []})["samples"]
               if all(x["labels"].get(k) == v for k, v in labels.items()))


def _how():
    return {h: _count(ASSEMBLED, how=h)
            for h in ("direct", "carried", "moved")}


def _graph(sc, name):
    frame = sc.io.Input([NamedVideoStream(sc, "t")])
    if name == "stencil":  # Histogram's rows reach back one: [-1, 0]
        return sc.ops.HistogramDelta(hist=sc.ops.Histogram(frame=frame))
    sliced = sc.streams.Slice(frame, partitions=[sc.partitioner.all(24)])
    return sc.streams.Unslice(sc.ops.Histogram(frame=sliced))


@pytest.mark.parametrize("cached", [False, True], ids=["host", "cached"])
@pytest.mark.parametrize("graph", ["stencil", "sliced"])
def test_chunks_are_decoded_where_they_ship_from(tmp_path, monkeypatch,
                                                 graph, cached):
    """A [-1, 0] stencil graph and a sliced graph, streamed: the same
    outputs as with stream_work_packets off; every fresh row of every
    chunk is counted once by how it came to lie in the chunk's array
    (scanner_tpu_load_assembled_rows_total{how}, the load:assemble
    span's args), decoded in its slot but for the stencil's back-reach,
    which is copied from the chunk before; no row goes through a
    buffer and an np.stack (rows always ascend: ColumnBatch takes no
    others).  With
    and without a frame-cache plan (the accelerator path of the CPU
    mesh, as tests/test_span_parts.py sets it up)."""
    import scanner_tpu.kernels  # noqa: F401
    from scanner_tpu.engine import framecache as fc
    if cached:
        monkeypatch.setenv("SCANNER_TPU_KERNEL_DEVICES", "all")
        monkeypatch.setenv("SCANNER_TPU_YUV_DEVICE", "force")
    was = fc.enabled()
    fc.set_enabled(cached)
    vid = str(tmp_path / "v.mp4")
    scv.synthesize_video(vid, num_frames=64, width=64, height=48, fps=24,
                         keyint=8)
    sc = Client(db_path=str(tmp_path / "db"), num_load_workers=1)
    try:
        sc.ingest_videos([("t", vid)])
        res = {}
        for stream in (True, False):
            how0 = _how()
            decoded0 = _count("scanner_tpu_decoded_frames_total")
            out = NamedStream(sc, f"o{int(stream)}")
            jid = sc.run(sc.io.Output(_graph(sc, graph), [out]),
                         PerfParams.manual(8, 32,
                                           stream_work_packets=stream),
                         cache_mode=CacheMode.Overwrite,
                         show_progress=False)
            res[stream] = [np.asarray(x) for x in out.load()]
            if not stream:
                assert _how() == how0  # the whole-task load assembles none
                continue
            how = {h: n - how0[h] for h, n in _how().items()}
            spans = [iv.args for p in sc.get_profile(jid).profilers
                     for iv in p.intervals()
                     if iv.name == "load:assemble" and "direct" in iv.args]
            assert sum(a["rows"] for a in spans) == sum(how.values()) >= 64
            for h in how:
                assert sum(a[h] for a in spans) == how[h]
            assert how["moved"] == 0
            assert not _count(ASSEMBLED, how="stacked")
            assert how["direct"] == \
                _count("scanner_tpu_decoded_frames_total") - decoded0
            # 2 tasks x 4 chunks: every chunk but a task's first reaches
            # back one row into the chunk before it
            assert how["carried"] == (6 if graph == "stencil" else 0)
        assert len(res[True]) == 64
        for a, b in zip(res[True], res[False]):
            assert np.array_equal(a, b)
    finally:
        sc.stop()
        fc.set_enabled(was)
        fc.cache().clear()


@pytest.fixture()
def feed_of(tmp_db, tmp_path):
    """A maker of _VideoFeeds over the reordered-B-frame clip, one for a
    list of chunks (each its table rows), as the streaming loader makes
    them; with the clip's frames by get_frames."""
    from scanner_tpu.engine.executor import LocalExecutor
    automata, _ = _clip(tmp_db, tmp_path, "bframe")
    a = automata()
    ref = a.get_frames(list(range(90)))
    a.close()
    ex = LocalExecutor(tmp_db)
    si = {"table": tmp_db.table_descriptor("bframe"), "column": "frame",
          "video_meta": scv.load_video_meta(tmp_db, "bframe")}
    task = SimpleNamespace(job=SimpleNamespace(job_idx=0), device=None)

    def make(chunks):
        plans = [SimpleNamespace(source_rows={0: np.asarray(c, np.int64)})
                 for c in chunks]
        return LocalExecutor._VideoFeed(ex, task, threading.local(), 0, si,
                                        plans, "rgb24")
    return make, ref


def test_a_chunk_with_repeated_rows_is_refused(feed_of):
    """Why the feed has no buffer-and-stack path: a chunk is a
    ColumnBatch, whose rows ascend strictly (the planner's source rows
    are a sorted set), so every row has one slot to be decoded into."""
    make, _ = feed_of
    with pytest.raises(ValueError, match="strictly increasing"):
        make([[5, 3, 3, 4]]).batch_for([5, 3, 3, 4])


def test_rows_decoded_off_their_slots_are_moved(feed_of, monkeypatch):
    """Where the session delivers other rows than the slots asked for,
    or in another order, the chunk is the same bytes: a head delivered
    late (the open-GOP retry, played here by a session that hands the
    first two rows of a call over last), and a chunk that skips rows a
    later one wants."""
    make, ref = feed_of

    def late_head(self, out, _real=StreamSession.decode_into):
        got = _real(self, out)
        if len(got) > 2:
            out[:len(got)] = np.roll(out[:len(got)], -2, axis=0)
            got = np.roll(got, -2)
        return got

    how0 = _how()
    chunks = [[0, 1, 2, 40, 41], [3, 4, 5, 42]]
    feed = make(chunks)
    for c in chunks:
        assert np.array_equal(np.asarray(feed.batch_for(c).data), ref[c])
    # [0, 1, 2] in place, 3 and 4 aside (held for the second chunk), 5
    # and 40 aside, 40 moved; then 41 and 42 in place
    assert {h: n - how0[h] for h, n in _how().items()} == \
        {"direct": 3 + 1 + 1, "carried": 3, "moved": 1}

    monkeypatch.setattr(StreamSession, "decode_into", late_head)
    how0 = _how()
    chunks = [list(range(20, 28)), list(range(27, 36))]
    feed = make(chunks)
    for c in chunks:
        assert np.array_equal(np.asarray(feed.batch_for(c).data), ref[c])
    assert {h: n - how0[h] for h, n in _how().items()} == \
        {"direct": 0, "carried": 1, "moved": 16}


def test_a_held_row_pins_its_chunk_no_longer_than_it_is_needed(feed_of):
    """Retention keeps rows, by _keep_from: the row a later chunk
    reaches back to is held (a view of its chunk's array) until that
    chunk has it, then let go, and the array with it."""
    make, _ = feed_of
    chunks = [[0, 1, 2, 3], [3, 4, 5, 6], [7, 8, 9]]
    feed = make(chunks)
    first = weakref.ref(feed.batch_for(chunks[0]).data)
    assert list(feed._held) == [3] and feed._held[3].base is first()
    feed.batch_for(chunks[1])
    assert not feed._held and first() is None
    feed.batch_for(chunks[2])
    assert not feed._held


_RSS_CHILD = r"""
import os, resource, sys, tempfile
import numpy as np
stream = sys.argv[1] == "1"
os.environ["SCANNER_TPU_STREAM_PACKETS"] = "1" if stream else "0"
root = tempfile.mkdtemp(prefix="rss_")
from scanner_tpu import (CacheMode, Client, NamedStream, NamedVideoStream,
                         PerfParams)
import scanner_tpu.kernels
from scanner_tpu import video as scv
vid = os.path.join(root, "big.mp4")
# 1600x1200 RGB = 5.8 MB/frame; 96-frame io packet = ~553 MB materialized
scv.synthesize_video(vid, num_frames=96, width=1600, height=1200, fps=24,
                     keyint=8)
sc = Client(db_path=os.path.join(root, "db"), num_load_workers=1)
sc.ingest_videos([("big", vid)])
frame = sc.io.Input([NamedVideoStream(sc, "big")])
out = NamedStream(sc, "h")
sc.run(sc.io.Output(sc.ops.Histogram(frame=frame), [out]),
       PerfParams.manual(8, 96), cache_mode=CacheMode.Overwrite,
       show_progress=False)
assert len(list(out.load())) == 96
sc.stop()
print("MAXRSS", resource.getrusage(resource.RUSAGE_SELF).ru_maxrss)
"""


@pytest.mark.slow
def test_streaming_bounds_peak_memory():
    """The 4K-memory claim, measured: one 96-frame 1600x1200 io packet
    (~553 MB decoded) run with 8-row chunks must peak far below the
    whole-packet run (reference element-cache bound)."""
    from scanner_tpu.util.jaxenv import cpu_only_env

    def rss(stream: bool) -> int:
        # n_devices=1: the child must NOT inherit the suite's 8-virtual-
        # device XLA_FLAGS — per-device buffers would dwarf (and equalize)
        # the decode-path memory this test measures
        r = subprocess.run(
            [sys.executable, "-c", _RSS_CHILD, "1" if stream else "0"],
            capture_output=True, text=True, timeout=420,
            env=cpu_only_env(n_devices=1), cwd=REPO)
        assert r.returncode == 0, r.stderr[-2000:]
        for ln in r.stdout.splitlines():
            if ln.startswith("MAXRSS"):
                return int(ln.split()[1])
        raise AssertionError(f"no MAXRSS in output: {r.stdout[-500:]}")

    peak_stream = rss(True)
    peak_whole = rss(False)
    # the whole-packet run holds the 553 MB batch (plus copies); the
    # streamed run holds a few 46 MB chunks, each written once: the
    # decoder's output is the chunk's array, with no scratch of a chunk
    # and a half beside it and no copy of the chunk on its way out (with
    # them the streamed run peaked 92 MB higher and missed this margin
    # by 40 MB; it is held with 50 MB to spare).  A decisive margin
    # rather than an exact model of the allocator.
    assert peak_stream < peak_whole - 310_000, \
        f"stream {peak_stream} kB vs whole {peak_whole} kB"
