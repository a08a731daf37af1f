"""Shape-stable kernel execution (engine/evaluate.py bucketed dispatch).

Three contracts are pinned here:

1. **Padding equivalence** — bucketed execution (pad tail chunks up to a
   power-of-two bucket, mask null rows through the call) is bit-identical
   to exact-shape execution for stateless, stencil, multi-output,
   stateful and null-interleaved kernels, across bucket boundaries and
   for tasks smaller than the smallest bucket.
2. **Shape-churn regression guard** — on the golden pipeline, each
   stdlib device op's distinct input-signature count (the
   scanner_tpu_op_recompiles_total proxy) stays bounded by its
   bucket-ladder size.  A future ragged call path fails here instead of
   silently re-tracing on TPU, where every new signature is seconds of
   XLA compile.
3. **Contiguous-range fast path** — ColumnBatch.take_rows/take_range
   slice [start, end) ranges directly (views) and agree with the
   general gather, nulls included.
"""

from typing import Any, Sequence, Tuple

import numpy as np
import pytest

from scanner_tpu import (CacheMode, Client, DeviceType, FrameType, Kernel,
                         NamedStream, NamedVideoStream, NullElement,
                         PerfParams, register_op)
import scanner_tpu.kernels  # noqa: F401  (registers Histogram)
from scanner_tpu import video as scv
from scanner_tpu.engine.batch import ColumnBatch
from scanner_tpu.engine.evaluate import bucket_for, bucket_ladder
from scanner_tpu.util.metrics import registry

N_FRAMES = 50
W, H = 64, 48


@pytest.fixture(scope="module")
def sc(tmp_path_factory):
    root = tmp_path_factory.mktemp("bucketing")
    vid = str(root / "v.mp4")
    scv.synthesize_video(vid, num_frames=N_FRAMES, width=W, height=H,
                         fps=24, keyint=12)
    client = Client(db_path=str(root / "db"))
    client.ingest_videos([("bk", vid)])
    yield client
    client.stop()


# ---------------------------------------------------------------------------
# ladder unit tests
# ---------------------------------------------------------------------------

def test_bucket_ladder_shape():
    assert bucket_ladder(1) == [1]
    assert bucket_ladder(4) == [4]
    assert bucket_ladder(6) == [4, 6]
    assert bucket_ladder(8) == [4, 8]
    assert bucket_ladder(16) == [4, 8, 16]
    assert bucket_ladder(100) == [4, 8, 16, 32, 64, 100]


def test_bucket_for_rounds_up():
    ladder = bucket_ladder(16)
    assert [bucket_for(k, ladder) for k in (1, 3, 4, 5, 8, 9, 16)] == \
        [4, 4, 4, 8, 8, 16, 16]


# ---------------------------------------------------------------------------
# padding-equivalence kernels (device-declared so the bucketed path
# engages; numpy-implemented so they run bit-exactly on the CPU backend)
# ---------------------------------------------------------------------------

@register_op(device=DeviceType.TPU, batch=16)
class BkStat(Kernel):
    """Stateless batched device kernel: per-row pixel sum."""

    calls: list = []  # batch sizes actually executed (shape probe)

    def execute(self, frame: Sequence[FrameType]) -> Sequence[Any]:
        BkStat.calls.append(len(frame))
        f = np.asarray(frame, np.int64)
        return f.reshape(len(f), -1).sum(axis=1)


@register_op(device=DeviceType.TPU, stencil=[-1, 0], batch=8)
class BkStencil(Kernel):
    """Stencil batched device kernel: sum over the 2-frame window."""

    def execute(self, frame: Sequence[Sequence[FrameType]]
                ) -> Sequence[Any]:
        a = np.asarray(frame, np.int64)  # (b, 2, H, W, C)
        return a.reshape(len(a), -1).sum(axis=1)


@register_op(device=DeviceType.TPU, batch=16)
class BkMulti(Kernel):
    """Multi-output batched device kernel: (array batch, per-row list)."""

    def execute(self, frame: Sequence[FrameType]) -> Tuple[Any, Any]:
        f = np.asarray(frame, np.int64)
        sums = f.reshape(len(f), -1).sum(axis=1)
        return sums, [int(s) % 251 for s in sums]


@register_op(device=DeviceType.TPU, batch=16, bounded_state=0)
class BkStateful(Kernel):
    """Stateful batched device kernel: running count across calls (the
    dispatcher must keep exact shapes here — padding rows would advance
    the count)."""

    def __init__(self, config):
        super().__init__(config)
        self._n = 0

    def reset(self):
        self._n = 0

    def execute(self, frame: Sequence[FrameType]) -> Sequence[Any]:
        out = [self._n + i for i in range(len(frame))]
        self._n += len(frame)
        return out


def _load(out):
    return list(out.load())


def _assert_rows_equal(a, b):
    assert len(a) == len(b)
    for i, (x, y) in enumerate(zip(a, b)):
        if isinstance(x, NullElement) or isinstance(y, NullElement):
            assert isinstance(x, NullElement) \
                and isinstance(y, NullElement), i
        elif isinstance(x, np.ndarray) or isinstance(y, np.ndarray):
            assert np.array_equal(np.asarray(x), np.asarray(y)), i
        else:
            assert x == y, i


def _run_ab(sc, monkeypatch, build, name, wp=8, io=16):
    """Run the same graph with exact shapes and with bucketed dispatch;
    return (exact_rows, bucketed_rows)."""
    outs = {}
    for mode, flag in (("exact", "0"), ("bucketed", "1")):
        monkeypatch.setenv("SCANNER_TPU_BUCKETED", flag)
        frame = sc.io.Input([NamedVideoStream(sc, "bk")])
        col = build(frame)
        out = NamedStream(sc, f"bk_{name}_{mode}")
        sc.run(sc.io.Output(col, [out]), PerfParams.manual(wp, io),
               cache_mode=CacheMode.Overwrite, show_progress=False)
        outs[mode] = _load(out)
    return outs["exact"], outs["bucketed"]


# rows counts straddle bucket boundaries: sub-smallest-bucket task (3),
# exact bucket (16), bucket+tail (21), full stream with ragged tail (50)
@pytest.mark.parametrize("rows", [3, 16, 21, N_FRAMES])
def test_padding_equivalence_stateless(sc, monkeypatch, rows):
    exact, bucketed = _run_ab(
        sc, monkeypatch,
        lambda f: sc.ops.BkStat(frame=sc.streams.Range(f, [(0, rows)])),
        f"stat{rows}")
    assert len(exact) == rows
    _assert_rows_equal(exact, bucketed)


def test_padding_pads_to_buckets(sc, monkeypatch):
    """The shape probe: bucketed execution only ever calls at ladder
    shapes; a 21-row task at wp=8 must not produce a 5-row call."""
    BkStat.calls = []
    monkeypatch.setenv("SCANNER_TPU_BUCKETED", "1")
    frame = sc.io.Input([NamedVideoStream(sc, "bk")])
    r = sc.streams.Range(frame, [(0, 21)])
    out = NamedStream(sc, "bk_probe")
    sc.run(sc.io.Output(sc.ops.BkStat(frame=r), [out]),
           PerfParams.manual(8, 16), cache_mode=CacheMode.Overwrite,
           show_progress=False)
    ladder = set(bucket_ladder(8))  # BkStat cap 16, wp 8 -> cap 8
    assert BkStat.calls and set(BkStat.calls) <= ladder, BkStat.calls
    assert len(_load(out)) == 21


def test_padding_equivalence_stencil(sc, monkeypatch):
    exact, bucketed = _run_ab(
        sc, monkeypatch,
        lambda f: sc.ops.BkStencil(frame=sc.streams.Range(f, [(0, 21)])),
        "stencil", wp=8, io=24)
    _assert_rows_equal(exact, bucketed)


@pytest.mark.parametrize("col", ["output0", "output1"])
def test_padding_equivalence_multi_output(sc, monkeypatch, col):
    exact, bucketed = _run_ab(
        sc, monkeypatch,
        lambda f: sc.ops.BkMulti(
            frame=sc.streams.Range(f, [(0, 21)]))[col],
        f"multi_{col}")
    _assert_rows_equal(exact, bucketed)


def test_padding_equivalence_stateful(sc, monkeypatch):
    """Stateful kernels keep exact call shapes under bucketed dispatch
    (padding would advance their state) — outputs stay identical."""
    exact, bucketed = _run_ab(
        sc, monkeypatch,
        lambda f: sc.ops.BkStateful(
            frame=sc.streams.Range(f, [(0, 21)])),
        "stateful")
    _assert_rows_equal(exact, bucketed)
    assert exact == list(range(21))  # state really did run row-by-row


def test_padding_equivalence_null_interleaved(sc, monkeypatch):
    """Null rows ride through the bucketed call at the full chunk shape
    and come out as NullElement — bit-identical to the exact path's
    live-subset call."""
    def build(f):
        r = sc.streams.Range(f, [(0, 6)])
        spaced = sc.streams.RepeatNull(r, [3])  # 18 rows, 12 null
        return sc.ops.BkStat(frame=spaced)

    exact, bucketed = _run_ab(sc, monkeypatch, build, "nulls")
    assert sum(isinstance(e, NullElement) for e in exact) == 12
    _assert_rows_equal(exact, bucketed)


# ---------------------------------------------------------------------------
# shape-churn regression guard (CI): stdlib device ops on the golden
# pipeline stay within their bucket ladder
# ---------------------------------------------------------------------------

def _op_counter(series: str):
    # sum across the device label: multi-chip runs split an op's count
    # over per-device samples, and a stale single-sample read would
    # alias another device's (unchanging) value
    snap = registry().snapshot()
    out: dict = {}
    for s in snap.get(series, {}).get("samples", []):
        op = s["labels"]["op"]
        out[op] = out.get(op, 0) + s["value"]
    return out


def test_shape_churn_guard_golden_pipeline(sc, monkeypatch):
    """Golden tier-1 pipeline (CPU backend, jit enabled): per device op,
    the distinct input-signature count of a bulk run — the
    scanner_tpu_op_recompiles_total delta — must stay within the op's
    bucket-ladder size, whatever the task/null geometry.  Tail work
    packets (50 % 16 = 2-row task) and null-thinned chunks must NOT
    mint signatures."""
    monkeypatch.delenv("SCANNER_TPU_BUCKETED", raising=False)
    wp, io = 8, 16
    ladder_size = len(bucket_ladder(wp))  # Histogram cap 16, wp 8 -> 8
    before = _op_counter("scanner_tpu_op_recompiles_total")

    # run 1: ragged tail geometry (tasks of 16,16,16,2 rows)
    frame = sc.io.Input([NamedVideoStream(sc, "bk")])
    hist = sc.ops.Histogram(frame=frame)
    out1 = NamedStream(sc, "guard_hist")
    sc.run(sc.io.Output(hist, [out1]), PerfParams.manual(wp, io),
           cache_mode=CacheMode.Overwrite, show_progress=False)

    # run 2: null-interleaved geometry (21 rows, 14 of them null)
    frame = sc.io.Input([NamedVideoStream(sc, "bk")])
    spaced = sc.streams.RepeatNull(
        sc.streams.Range(frame, [(0, 7)]), [3])
    hist2 = sc.ops.Histogram(frame=spaced)
    out2 = NamedStream(sc, "guard_hist_null")
    sc.run(sc.io.Output(hist2, [out2]), PerfParams.manual(wp, io),
           cache_mode=CacheMode.Overwrite, show_progress=False)

    after = _op_counter("scanner_tpu_op_recompiles_total")
    for op in ("Histogram",):
        # each run builds a fresh evaluator (fresh signature set), so
        # the two runs may each contribute up to one ladder of sigs
        delta = after.get(op, 0) - before.get(op, 0)
        assert 0 < delta <= 2 * ladder_size, (
            f"{op}: {delta} distinct shape signatures across two runs "
            f"(bucket ladder size {ladder_size} per run) — a ragged "
            f"call path is re-tracing")
    # outputs stay correct under the guard geometry
    assert len(_load(out1)) == N_FRAMES
    rows2 = _load(out2)
    assert len(rows2) == 21
    assert sum(isinstance(e, NullElement) for e in rows2) == 14


def test_shape_churn_guard_fused_chains(sc, monkeypatch):
    """Fusion extension of the shape-churn guard (PERF.md §3):
    on the golden fusable pipeline under the same ragged-tail +
    null-interleaved geometry sweep, (a) the fused chain's distinct
    input-signature count stays within ITS bucket ladder — chains obey
    the same ladder contract as single ops — and (b) the total number
    of executables minted across the graph strictly DECREASES fused vs
    staged: one program per chain rung replaces one per member per
    rung."""
    from scanner_tpu.graph import fusion

    monkeypatch.delenv("SCANNER_TPU_BUCKETED", raising=False)
    wp, io = 8, 16
    # HistDiff (windowed, non-head) stays staged and mints its own
    # ladder in BOTH modes; the chain covers the other three
    cid = "Resize+Blur+Histogram"
    members = ("Resize", "Blur", "Histogram", "HistDiff")

    def sweep(tag):
        """§5 ragged sweep: run 1 tail geometry (16,16,16,2 row tasks),
        run 2 null-interleaved (21 rows, 14 null)."""
        frame = sc.io.Input([NamedVideoStream(sc, "bk")])
        small = sc.ops.Resize(frame=frame, width=[32], height=[24])
        blur = sc.ops.Blur(frame=small, kernel_size=3, sigma=1.1)
        hist = sc.ops.Histogram(frame=blur)
        diff = sc.ops.HistDiff(frame=hist)
        sc.run(sc.io.Output(diff, [NamedStream(sc, f"guard_fz_{tag}1")]),
               PerfParams.manual(wp, io),
               cache_mode=CacheMode.Overwrite, show_progress=False)
        frame = sc.io.Input([NamedVideoStream(sc, "bk")])
        spaced = sc.streams.RepeatNull(
            sc.streams.Range(frame, [(0, 7)]), [3])
        small = sc.ops.Resize(frame=spaced, width=[32], height=[24])
        blur = sc.ops.Blur(frame=small, kernel_size=3, sigma=1.1)
        hist = sc.ops.Histogram(frame=blur)
        diff = sc.ops.HistDiff(frame=hist)
        sc.run(sc.io.Output(diff, [NamedStream(sc, f"guard_fz_{tag}2")]),
               PerfParams.manual(wp, io),
               cache_mode=CacheMode.Overwrite, show_progress=False)

    def minted(before, after, keys):
        return sum(after.get(k, 0) - before.get(k, 0) for k in keys)

    prev = fusion.enabled()
    try:
        fusion.set_enabled(True)
        before = _op_counter("scanner_tpu_op_recompiles_total")
        sweep("fused")
        after = _op_counter("scanner_tpu_op_recompiles_total")
        chain_delta = after.get(cid, 0) - before.get(cid, 0)
        # each run builds a fresh evaluator, so two runs may each mint
        # up to one chain ladder (cap <= wp => ladder(cap) <= ladder(wp))
        ladder_size = len(bucket_ladder(wp))
        assert 0 < chain_delta <= 2 * ladder_size, (
            f"{cid}: {chain_delta} signatures across the sweep "
            f"(<= {2 * ladder_size} allowed) — the fused path is "
            f"re-tracing")
        fused_total = minted(before, after, (cid,) + members)

        fusion.set_enabled(False)
        before = _op_counter("scanner_tpu_op_recompiles_total")
        sweep("staged")
        after = _op_counter("scanner_tpu_op_recompiles_total")
        staged_total = minted(before, after, (cid,) + members)
    finally:
        fusion.set_enabled(prev)

    assert fused_total < staged_total, (
        f"fusion must strictly reduce minted executables: fused "
        f"{fused_total} vs staged {staged_total}")
    # fused outputs stay correct under the guard geometry (HistDiff's
    # [-1, 0] stencil nullifies every live row whose window touches a
    # null neighbor: of the 7 live rows only row 0 — REPEAT_EDGE-
    # clamped onto itself — survives)
    assert len(_load(NamedStream(sc, "guard_fz_fused1"))) == N_FRAMES
    rows2 = _load(NamedStream(sc, "guard_fz_fused2"))
    assert len(rows2) == 21
    assert sum(isinstance(e, NullElement) for e in rows2) == 20


def test_recompile_signature_includes_dtype(monkeypatch):
    """Two calls with equal shapes but different dtypes are distinct XLA
    executables — the recompile proxy must count both (it used to key on
    shape alone and undercount, e.g. uint8 vs float32 after a
    conversion)."""
    from scanner_tpu.engine.evaluate import TaskEvaluator
    from scanner_tpu.graph import analysis as A
    from scanner_tpu.graph import ops as O
    from scanner_tpu.graph.streams_dsl import IOGenerator
    from scanner_tpu.util.profiler import Profiler

    monkeypatch.setenv("SCANNER_TPU_BUCKETED", "1")
    monkeypatch.setenv("SCANNER_TPU_PRECOMPILE", "0")

    class _Src:
        is_video = False

    io_g = IOGenerator()
    frame = io_g.Input([_Src()])
    col = O.OpGenerator().BkStat(frame=frame)
    outp = io_g.Output(col, [_Src()])
    info = A.analyze([outp])
    src = info.sources[0]
    jr = A.job_rows(info, 0, {src.id: 8})
    jr.work_packet_size = 8
    plan = A.derive_task_streams(info, jr, (0, 8))
    te = TaskEvaluator(info, Profiler())
    try:
        before = _op_counter(
            "scanner_tpu_op_recompiles_total").get("BkStat", 0)
        rows = np.arange(8, dtype=np.int64)
        for dtype in (np.uint8, np.float32):
            batch = ColumnBatch(rows, np.zeros((8, 4, 4, 3), dtype))
            res = te.execute_task(jr, plan, {src.id: batch})
            assert all(len(b) == 8 for b in res.values())
        after = _op_counter(
            "scanner_tpu_op_recompiles_total").get("BkStat", 0)
        assert after - before == 2, (
            "equal shapes with different dtypes must count as two "
            "signatures")
    finally:
        te.close()


# ---------------------------------------------------------------------------
# ladder precompile (warm-up)
# ---------------------------------------------------------------------------

def test_precompile_warms_ladder(sc, monkeypatch):
    """SCANNER_TPU_PRECOMPILE=1 forces the setup-time ladder warm-up
    (CPU backend): every device op's ladder compiles on the background
    thread and the per-op precompile gauge appears."""
    from scanner_tpu.engine.evaluate import TaskEvaluator
    from scanner_tpu.graph import analysis as A
    from scanner_tpu.util.profiler import Profiler

    monkeypatch.setenv("SCANNER_TPU_PRECOMPILE", "1")
    monkeypatch.delenv("SCANNER_TPU_BUCKETED", raising=False)
    frame = sc.io.Input([NamedVideoStream(sc, "bk")])
    hist = sc.ops.Histogram(frame=frame)
    outp = sc.io.Output(hist, [NamedStream(sc, "warm_direct")])
    info = A.analyze([outp])
    te = TaskEvaluator(info, Profiler(), precompile=(H, W, 8))
    try:
        assert te._precompile_thread is not None
        te._precompile_thread.join(timeout=60)
        assert not te._precompile_thread.is_alive()
        warmed = _op_counter("scanner_tpu_op_precompile_seconds")
        assert "Histogram" in warmed
        assert warmed["Histogram"] >= 0.0
        for ki in te.kernels.values():
            assert ki._warm_state in ("done", "idle")
    finally:
        te.close()


def test_precompile_skips_geometry_changed_inputs(sc, monkeypatch):
    """An op downstream of a geometry-changing kernel (Resize) must not
    warm at the SOURCE geometry — that would compile a ladder of
    wrong-shape executables and stall the first real call behind them.
    First-hop consumers of source frames stay warmable.  (Fusion off:
    this pins the STAGED warm-up contract — fused, Resize+Histogram
    becomes one chain that legitimately warms through the geometry
    change; test_fusion.py covers that side.)"""
    from scanner_tpu.engine.evaluate import TaskEvaluator
    from scanner_tpu.graph import analysis as A
    from scanner_tpu.graph import fusion
    from scanner_tpu.util.profiler import Profiler

    monkeypatch.setenv("SCANNER_TPU_PRECOMPILE", "1")
    frame = sc.io.Input([NamedVideoStream(sc, "bk")])
    small = sc.ops.Resize(frame=frame, width=[32], height=[24])
    hist = sc.ops.Histogram(frame=small)
    outp = sc.io.Output(hist, [NamedStream(sc, "warm_skip")])
    info = A.analyze([outp])
    prev = fusion.enabled()
    fusion.set_enabled(False)
    try:
        te = TaskEvaluator(info, Profiler(), precompile=(H, W, 8))
    finally:
        fusion.set_enabled(prev)
    try:
        states = {ki.node.name: ki._warm_state
                  for ki in te.kernels.values()}
        assert states["Histogram"] == "idle"   # geometry unknown: skip
        assert states["Resize"] != "idle"      # source frames: warmable
        if te._precompile_thread is not None:
            te._precompile_thread.join(timeout=60)
    finally:
        te.close()


def test_precompile_of_stream_bound_kernel_completes(sc, monkeypatch,
                                                     caplog):
    """A staged Resize gets its output size from new_stream: the ladder
    warm-up must run on a kernel already bound to job 0's stream args
    (it raised ZeroDivisionError on a 0x0 size from PR 2 until the v5e
    bring-up, logged at debug).  The whole ladder compiles, no
    'precompile of Resize ... failed' warning is logged, the precompile
    gauge is set, and the warming gauge is back at its resting value."""
    import logging

    from scanner_tpu.engine.evaluate import TaskEvaluator
    from scanner_tpu.graph import analysis as A
    from scanner_tpu.graph import fusion
    from scanner_tpu.util.metrics import registry
    from scanner_tpu.util.profiler import Profiler

    def warming():
        entry = registry().snapshot()["scanner_tpu_evaluator_warming"]
        return sum(smp["value"] for smp in entry["samples"])

    monkeypatch.setenv("SCANNER_TPU_PRECOMPILE", "1")
    monkeypatch.delenv("SCANNER_TPU_BUCKETED", raising=False)
    frame = sc.io.Input([NamedVideoStream(sc, "bk")])
    small = sc.ops.Resize(frame=frame, width=[32], height=[24])
    outp = sc.io.Output(small, [NamedStream(sc, "warm_resize")])
    info = A.analyze([outp])
    rest = warming()
    prev = fusion.enabled()
    fusion.set_enabled(False)
    try:
        with caplog.at_level(logging.WARNING, logger="scanner_tpu"):
            te = TaskEvaluator(info, Profiler(), precompile=(H, W, 8))
            try:
                assert te._precompile_thread is not None
                te._precompile_thread.join(timeout=120)
                assert not te._precompile_thread.is_alive()
            finally:
                te.close()
    finally:
        fusion.set_enabled(prev)
    assert "precompile of" not in caplog.text, caplog.text
    assert "Resize" in _op_counter("scanner_tpu_op_precompile_seconds")
    assert warming() == rest


def test_precompile_claim_beats_warmup(sc, monkeypatch):
    """A real call racing ahead of the warm-up thread claims the kernel:
    ensure_warm() never deadlocks and the warm-up skips it."""
    from scanner_tpu.engine.evaluate import KernelInstance

    monkeypatch.setenv("SCANNER_TPU_PRECOMPILE", "1")
    frame = sc.io.Input([NamedVideoStream(sc, "bk")])
    node = sc.ops.Histogram(frame=frame).op
    from scanner_tpu.util.profiler import Profiler
    ki = KernelInstance(node, Profiler())
    ki.setup()
    try:
        ki._warm_state = "pending"
        ki.ensure_warm()                       # claims
        assert ki._warm_state == "done"
        ki.precompile([4, 8], H, W)            # must skip, not re-run
        assert ki._warm_state == "done"        # and never deadlock
    finally:
        ki.close()


# ---------------------------------------------------------------------------
# contiguous-range fast path (ColumnBatch.take_rows / take_range)
# ---------------------------------------------------------------------------

def _mk_batch(rows, with_nulls=False):
    rows = np.asarray(rows, np.int64)
    data = (np.arange(len(rows) * 3).reshape(len(rows), 3)
            + rows[:, None] * 100)
    nulls = None
    if with_nulls:
        nulls = np.zeros(len(rows), bool)
        nulls[::3] = True
    return ColumnBatch(rows, data, nulls)


def test_take_range_contiguous_is_view():
    b = _mk_batch(np.arange(10, 30))
    out = b.take_range(14, 22)
    assert np.array_equal(out.rows, np.arange(14, 22))
    assert np.array_equal(out.data, b.data[4:12])
    # direct slice, not a gather copy
    assert out.data.base is b.data or out.data.base is b.data.base


def test_take_rows_fast_path_matches_gather():
    b = _mk_batch(np.arange(10, 30), with_nulls=True)
    rows = np.arange(14, 22)
    want = b.take(b.positions(rows), rows)
    got = b.take_rows(rows)
    assert np.array_equal(got.rows, want.rows)
    assert np.array_equal(got.data, want.data)
    assert np.array_equal(got.nulls, want.nulls)


def test_take_range_gapped_rows_fall_back():
    # rows with a hole: the fast path must detect the gap and gather
    b = _mk_batch(np.asarray([0, 1, 2, 5, 6, 7]))
    with pytest.raises(KeyError):
        b.take_range(0, 6)  # rows 3,4 missing
    out = b.take_range(5, 8)
    assert np.array_equal(out.rows, np.asarray([5, 6, 7]))
    assert np.array_equal(out.data, b.data[3:])


def test_take_rows_non_contiguous_unchanged():
    b = _mk_batch(np.arange(0, 40, 2))  # even rows only
    out = b.take_rows(np.asarray([0, 4, 10]))
    assert np.array_equal(out.data, b.data[[0, 2, 5]])
