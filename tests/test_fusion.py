"""Whole-pipeline XLA fusion (graph/fusion.py planner +
engine/evaluate.py FusedKernelInstance).

Contracts pinned here:

1. **Planner** — maximal runs of fusable device ops form chains; host
   ops, stateful kernels, explicit ``fuse=False`` overrides, missing
   cost() models, and externally-consumed intermediates break chains;
   a run of one stays staged.
2. **Bit-exact equivalence** — the fused chain program produces exactly
   the staged per-op pipeline's rows: stateless chains, stencil
   composition (head and tail stencils), null-interleaved domains,
   bucket-boundary/tail geometries, Gather-sampled domains, and the
   virtual multi-chip staging path.
3. **One ladder per chain** — a fused run mints recompile signatures
   under the CHAIN id only (bounded by the chain's ladder), members
   mint none; the compile ledger records the member list; precompile
   warms the chain ladder.
"""

import dataclasses
from typing import Any, Sequence, Tuple

import numpy as np
import pytest

from scanner_tpu import (CacheMode, Client, DeviceType, FrameType, Kernel,
                         NamedStream, NamedVideoStream, NullElement,
                         PerfParams, register_op)
import scanner_tpu.kernels  # noqa: F401  (registers the stdlib ops)
from scanner_tpu import video as scv
from scanner_tpu.engine import evaluate as ev
from scanner_tpu.engine.batch import ColumnBatch
from scanner_tpu.engine.evaluate import bucket_ladder
from scanner_tpu.graph import analysis as A
from scanner_tpu.graph import fusion
from scanner_tpu.graph import ops as O
from scanner_tpu.graph.streams_dsl import IOGenerator
from scanner_tpu.common import JobException
from scanner_tpu.util import coststats as _cs
from scanner_tpu.util.profiler import Profiler
from scanner_tpu.util.metrics import registry

N_FRAMES = 50
W, H = 64, 48

io = IOGenerator()
ops = O.OpGenerator()


@pytest.fixture(autouse=True)
def _drop_cache_pages():
    """The e2e runs stage through the global frame cache; drop its
    resident pages afterwards so this module's deliberate residency
    doesn't dominate later modules' ledger-top assertions
    (tests/test_memstats.py reads global top_entries)."""
    yield
    import scanner_tpu.engine.framecache as _fc
    if _fc._CACHE is not None:
        _fc._CACHE.clear()


class FakeStream:
    is_video = False

    def __init__(self, n):
        self.n = n


# -- planner fixtures: minimal fusable / non-fusable op classes -------------

@register_op(name="FzA", device=DeviceType.TPU, batch=8)
class _FzA(Kernel):
    def cost(self, shapes):
        return {"flops": 1.0, "bytes_in": 1.0, "bytes_out": 1.0}

    def execute(self, frame: Sequence[FrameType]) -> Sequence[FrameType]:
        return np.asarray(frame)  # pragma: no cover


@register_op(name="FzB", device=DeviceType.TPU, batch=8)
class _FzB(Kernel):
    def cost(self, shapes):
        return {"flops": 1.0, "bytes_in": 1.0, "bytes_out": 1.0}

    def execute(self, frame: Sequence[FrameType]) -> Sequence[FrameType]:
        return np.asarray(frame)  # pragma: no cover


@register_op(name="FzC", device=DeviceType.TPU, batch=8)
class _FzC(Kernel):
    def cost(self, shapes):
        return {"flops": 1.0, "bytes_in": 1.0, "bytes_out": 1.0}

    def execute(self, frame: Sequence[FrameType]) -> Sequence[FrameType]:
        return np.asarray(frame)  # pragma: no cover


@register_op(name="FzHost", device=DeviceType.CPU, batch=8)
class _FzHost(Kernel):
    def cost(self, shapes):
        return {"flops": 1.0, "bytes_in": 1.0, "bytes_out": 1.0}

    def execute(self, frame: Sequence[FrameType]) -> Sequence[FrameType]:
        return np.asarray(frame)  # pragma: no cover


@register_op(name="FzState", device=DeviceType.TPU, batch=8,
             bounded_state=0)
class _FzState(Kernel):
    def cost(self, shapes):
        return {"flops": 1.0, "bytes_in": 1.0, "bytes_out": 1.0}

    def execute(self, frame: Sequence[FrameType]) -> Sequence[FrameType]:
        return np.asarray(frame)  # pragma: no cover


@register_op(name="FzNoCost", device=DeviceType.TPU, batch=8)
class _FzNoCost(Kernel):
    def execute(self, frame: Sequence[FrameType]) -> Sequence[FrameType]:
        return np.asarray(frame)  # pragma: no cover


@register_op(name="FzTwo", device=DeviceType.TPU, batch=8)
class _FzTwo(Kernel):
    def execute(self, frame: Sequence[FrameType]) -> Tuple[Any, Any]:
        return np.zeros(len(frame))  # one column of the two declared


def _info(*mk):
    """Build Input -> mk[0] -> mk[1] -> ... -> Output and analyze it."""
    col = io.Input([FakeStream(24)])
    for f in mk:
        col = f(col)
    return A.analyze([io.Output(col, [FakeStream(0)])])


# ---------------------------------------------------------------------------
# planner units
# ---------------------------------------------------------------------------

def test_plan_basic_chain():
    info = _info(lambda c: ops.FzA(frame=c), lambda c: ops.FzB(frame=c),
                 lambda c: ops.FzC(frame=c))
    chains = fusion.plan_chains(info)
    assert len(chains) == 1
    ch = chains[0]
    assert ch.member_names == ["FzA", "FzB", "FzC"]
    assert ch.chain_id == "FzA+FzB+FzC"
    assert ch.head.name == "FzA" and ch.tail.name == "FzC"
    assert ch.windows() == [0, 0, 0] and ch.width() == 1


def test_plan_breaks_at_host_op():
    info = _info(lambda c: ops.FzA(frame=c),
                 lambda c: ops.FzHost(frame=c),
                 lambda c: ops.FzB(frame=c))
    assert fusion.plan_chains(info) == []


def test_plan_breaks_at_stateful():
    info = _info(lambda c: ops.FzA(frame=c),
                 lambda c: ops.FzState(frame=c),
                 lambda c: ops.FzB(frame=c))
    assert fusion.plan_chains(info) == []


def test_plan_breaks_at_fuse_false():
    # fuse=False mid-run splits it; the two halves are singletons
    info = _info(lambda c: ops.FzA(frame=c),
                 lambda c: ops.FzB(frame=c, fuse=False),
                 lambda c: ops.FzC(frame=c))
    assert fusion.plan_chains(info) == []
    # fuse=False at the tail keeps the upstream pair
    info = _info(lambda c: ops.FzA(frame=c), lambda c: ops.FzB(frame=c),
                 lambda c: ops.FzC(frame=c, fuse=False))
    chains = fusion.plan_chains(info)
    assert [c.member_names for c in chains] == [["FzA", "FzB"]]


def test_plan_breaks_at_missing_cost():
    info = _info(lambda c: ops.FzA(frame=c),
                 lambda c: ops.FzNoCost(frame=c),
                 lambda c: ops.FzB(frame=c))
    assert fusion.plan_chains(info) == []


def test_plan_breaks_at_external_consumer():
    # FzB's output is read by BOTH FzC and the second Output: it must
    # materialize, so the chain ends at FzB
    col = io.Input([FakeStream(24)])
    a = ops.FzA(frame=col)
    b = ops.FzB(frame=a)
    c = ops.FzC(frame=b)
    info = A.analyze([io.Output(c, [FakeStream(0)]),
                      io.Output(b, [FakeStream(0)])])
    chains = fusion.plan_chains(info)
    assert [ch.member_names for ch in chains] == [["FzA", "FzB"]]


def test_golden_chain_geometry():
    """The golden pipeline plans Resize+Blur+Histogram; HistDiff's
    [-1, 0] window keeps it OUT of the chain (a windowed op may only
    HEAD a chain — mid-chain it would make the fused program recompute
    every upstream member once per window element, where the staged
    stencil cache computes each intermediate row exactly once)."""
    col = io.Input([FakeStream(24)])
    r = ops.Resize(frame=col, width=[32], height=[24])
    b = ops.Blur(frame=r, kernel_size=3, sigma=1.0)
    h = ops.Histogram(frame=b)
    d = ops.HistDiff(frame=h)
    info = A.analyze([io.Output(d, [FakeStream(0)])])
    chains = fusion.plan_chains(info)
    assert len(chains) == 1
    ch = chains[0]
    assert ch.chain_id == "Resize+Blur+Histogram"
    assert ch.windows() == [0, 0, 0]
    assert ch.width() == 1
    assert "HistDiff" not in ch.member_names


def test_plan_windowed_op_only_heads_a_chain():
    """A stencil op extends no chain, but may start one: as the head
    its window composes into the chain's input gather (the same rows
    the staged path read)."""
    col = io.Input([FakeStream(24)])
    a = ops.FzA(frame=col)
    d = ops.HistDiff(frame=a)       # windowed: breaks the extension
    c = ops.FzB(frame=d)
    info = A.analyze([io.Output(c, [FakeStream(0)])])
    chains = fusion.plan_chains(info)
    # HistDiff itself heads a chain with FzB; FzA stays a singleton
    assert [ch.member_names for ch in chains] == [["HistDiff", "FzB"]]
    assert chains[0].windows() == [2, 0]
    assert chains[0].width() == 2


# ---------------------------------------------------------------------------
# the one call loop (TaskEvaluator._run_op) names what it ran
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("graph,label,text", [
    ("op", "Histogram", "missing output rows [24]..."),
    ("chain", "Blur+Histogram", "missing output rows [24]..."),
    ("two", "FzTwo", "expected 2-tuple output"),
], ids=["op", "chain", "tuple"])
def test_the_call_loop_names_its_op_or_chain_in_errors(monkeypatch, graph,
                                                        label, text):
    """A staged op and a chain go through one body and fail in its
    words, naming the op or the chain id: rows asked for that the calls
    did not make, a result of fewer columns than declared."""
    monkeypatch.setenv("SCANNER_TPU_PRECOMPILE", "0")
    col = io.Input([FakeStream(24)])
    if graph == "chain":
        col = ops.Blur(frame=col, kernel_size=3, sigma=1.0)
    col = ops.FzTwo(frame=col).outputs[0] if graph == "two" \
        else ops.Histogram(frame=col)
    info = A.analyze([io.Output(col, [FakeStream(0)])])
    te = ev.TaskEvaluator(info, Profiler())
    try:
        assert [f.chain_id for f in te.fused.values()] \
            == ([label] if graph == "chain" else [])
        src = info.sources[0]
        jr = A.job_rows(info, 0, {src.id: 24})
        plan = A.derive_task_streams(info, jr, (0, 16), task_idx=0)
        if graph != "two":
            # one row asked for past what the task computes
            tail = col.op.id
            ts = plan.streams[tail]
            plan.streams[tail] = dataclasses.replace(
                ts, valid_output_rows=np.append(ts.valid_output_rows, 24))
        rows = np.asarray(plan.source_rows[src.id], np.int64)
        with pytest.raises(JobException) as err:
            te.execute_task(jr, plan, {src.id: ColumnBatch(
                rows, np.zeros((len(rows), 16, 16, 3), np.uint8))})
        assert str(err.value) == f"{label}: {text}"
    finally:
        te.close()


# ---------------------------------------------------------------------------
# end-to-end equivalence (fused vs staged, CPU backend)
# ---------------------------------------------------------------------------

@pytest.fixture(scope="module")
def sc(tmp_path_factory):
    root = tmp_path_factory.mktemp("fusion")
    vid = str(root / "v.mp4")
    scv.synthesize_video(vid, num_frames=N_FRAMES, width=W, height=H,
                         fps=24, keyint=12)
    client = Client(db_path=str(root / "db"))
    client.ingest_videos([("fz", vid)])
    yield client
    client.stop()


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


def _run_ab(sc, build, name, wp=8, io_=16):
    """Run the same graph staged (fusion off) and fused; return
    (staged_rows, fused_rows)."""
    outs = {}
    for mode, on in (("staged", False), ("fused", True)):
        fusion.set_enabled(on)
        try:
            frame = sc.io.Input([NamedVideoStream(sc, "fz")])
            col = build(sc, frame)
            out = NamedStream(sc, f"fz_{name}_{mode}")
            sc.run(sc.io.Output(col, [out]), PerfParams.manual(wp, io_),
                   cache_mode=CacheMode.Overwrite, show_progress=False)
            outs[mode] = _load(out)
        finally:
            fusion.set_enabled(True)
    return outs["staged"], outs["fused"]


def _golden(s, frame):
    r = s.ops.Resize(frame=frame, width=[32], height=[24])
    b = s.ops.Blur(frame=r, kernel_size=3, sigma=1.1)
    h = s.ops.Histogram(frame=b)
    return s.ops.HistDiff(frame=h)


def _op_counter(series: str):
    snap = registry().snapshot()
    out = {}
    for s in snap.get(series, {}).get("samples", []):
        lab = s["labels"]
        out[lab.get("op") or lab.get("chain")] = \
            out.get(lab.get("op") or lab.get("chain"), 0) + s["value"]
    return out


# rows straddle bucket boundaries: sub-smallest-bucket task (3), exact
# bucket (16), bucket+tail (21), full stream with ragged tail (50)
@pytest.mark.parametrize("rows", [3, 16, 21, N_FRAMES])
def test_fused_equivalence_golden_chain(sc, rows):
    def build(s, f):
        if rows < N_FRAMES:
            f = s.streams.Range(f, [(0, rows)])
        return _golden(s, f)

    staged, fused = _run_ab(sc, build, f"golden{rows}")
    assert len(fused) == rows
    _assert_rows_equal(staged, fused)


def test_fused_equivalence_stencil_head(sc):
    """Stencil member at the chain HEAD (OpticalFlow's [-1, 0] window
    feeds Blur): the composed gather reads the window once and the
    flow field never materializes."""
    def build(s, f):
        flow = s.ops.OpticalFlow(frame=s.streams.Range(f, [(0, 12)]))
        return s.ops.Blur(frame=flow, kernel_size=3, sigma=0.8)

    staged, fused = _run_ab(sc, build, "flowblur", wp=4)
    assert len(fused) == 12
    _assert_rows_equal(staged, fused)


def test_fused_equivalence_null_interleaved(sc):
    """Null rows propagate through the composed window: a tail row is
    null iff ANY transitively-read head row is null — identical to the
    staged member-by-member propagation."""
    def build(s, f):
        spaced = s.streams.RepeatNull(s.streams.Range(f, [(0, 6)]), [3])
        return _golden(s, spaced)

    staged, fused = _run_ab(sc, build, "nulls")
    assert sum(isinstance(e, NullElement) for e in staged) > 0
    _assert_rows_equal(staged, fused)


def test_fused_equivalence_gather_sampled(sc):
    def build(s, f):
        g = s.streams.Gather(f, [[0, 7, 8, 23, 24, 49]])
        return _golden(s, g)

    staged, fused = _run_ab(sc, build, "gather")
    assert len(fused) == 6
    _assert_rows_equal(staged, fused)


def test_fused_equivalence_multichip(sc, monkeypatch):
    """Virtual multi-chip staging (the PR 5 affinity lever): fused
    chains stage the head input to the instance's assigned chip and
    stay bit-exact."""
    monkeypatch.setenv("SCANNER_TPU_KERNEL_DEVICES", "all")
    staged, fused = _run_ab(sc, _golden, "mchip")
    assert len(fused) == N_FRAMES
    _assert_rows_equal(staged, fused)


def test_fusion_kill_switch_restores_staged_metrics(sc):
    """With fusion disabled the evaluator plans no chains and members
    dispatch individually — the chain id never shows up in op
    metrics."""
    before = _op_counter("scanner_tpu_op_rows_total")
    fusion.set_enabled(False)
    try:
        frame = sc.io.Input([NamedVideoStream(sc, "fz")])
        out = NamedStream(sc, "fz_kill")
        sc.run(sc.io.Output(_golden(sc, frame), [out]),
               PerfParams.manual(8, 16),
               cache_mode=CacheMode.Overwrite, show_progress=False)
    finally:
        fusion.set_enabled(True)
    after = _op_counter("scanner_tpu_op_rows_total")
    cid = "Resize+Blur+Histogram"
    assert after.get(cid, 0) == before.get(cid, 0)
    assert after.get("Resize", 0) > before.get("Resize", 0)


# ---------------------------------------------------------------------------
# chain-level attribution: one ladder, member'd ledger, warm chains
# ---------------------------------------------------------------------------

def test_one_ladder_per_chain_and_silent_members(sc):
    """A fused run mints recompile signatures under the CHAIN id only,
    bounded by the chain ladder; the chain members mint none and never
    dispatch.  HistDiff stays staged (windowed, non-head) and keeps its
    own row accounting."""
    cid = "Resize+Blur+Histogram"
    wp = 8
    before_rc = _op_counter("scanner_tpu_op_recompiles_total")
    before_rows = _op_counter("scanner_tpu_op_rows_total")
    frame = sc.io.Input([NamedVideoStream(sc, "fz")])
    out = NamedStream(sc, "fz_ladder")
    sc.run(sc.io.Output(_golden(sc, frame), [out]),
           PerfParams.manual(wp, 16),
           cache_mode=CacheMode.Overwrite, show_progress=False)
    after_rc = _op_counter("scanner_tpu_op_recompiles_total")
    after_rows = _op_counter("scanner_tpu_op_rows_total")
    delta = after_rc.get(cid, 0) - before_rc.get(cid, 0)
    assert 0 < delta <= len(bucket_ladder(wp))
    for member in ("Resize", "Blur", "Histogram"):
        assert after_rc.get(member, 0) == before_rc.get(member, 0), member
        assert after_rows.get(member, 0) == before_rows.get(member, 0), \
            member
    assert after_rows.get(cid, 0) - before_rows.get(cid, 0) >= N_FRAMES
    # the staged tail op still dispatches under its own name
    assert after_rows.get("HistDiff", 0) - before_rows.get(
        "HistDiff", 0) >= N_FRAMES


def test_compile_ledger_records_members(sc):
    """observe_compiles entries for a fused chain carry the member op
    list (the fused-compile attribution satellite)."""
    was = _cs.enabled()
    _cs.set_enabled(True)
    try:
        frame = sc.io.Input([NamedVideoStream(sc, "fz")])
        out = NamedStream(sc, "fz_ledger")
        sc.run(sc.io.Output(_golden(sc, frame), [out]),
               PerfParams.manual(8, 16),
               cache_mode=CacheMode.Overwrite, show_progress=False)
    finally:
        _cs.set_enabled(was)
    cid = "Resize+Blur+Histogram"
    entries = [e for e in _cs.compile_ledger(10_000) if e["op"] == cid]
    assert entries, "no ledger entries under the chain id"
    assert all(e.get("members") == ["Resize", "Blur", "Histogram"]
               for e in entries)


def test_precompile_warms_chain(sc, monkeypatch):
    """The warm-up thread precompiles ONE chain ladder (not the member
    ladders): the precompile gauge appears under the chain id, the
    members stay unwarmed individually, and a geometry change INSIDE
    the chain (Resize head) is warmable — the chain traces through it
    from source-geometry head frames."""
    from scanner_tpu.engine.evaluate import TaskEvaluator
    from scanner_tpu.util.profiler import Profiler

    monkeypatch.setenv("SCANNER_TPU_PRECOMPILE", "1")
    cid = "Resize+Blur+Histogram"
    frame = sc.io.Input([NamedVideoStream(sc, "fz")])
    r = sc.ops.Resize(frame=frame, width=[32], height=[24])
    b = sc.ops.Blur(frame=r, kernel_size=3, sigma=1.1)
    h = sc.ops.Histogram(frame=b)
    outp = sc.io.Output(h, [NamedStream(sc, "fz_warm")])
    info = A.analyze([outp])
    te = TaskEvaluator(info, Profiler(), precompile=(H, W, 8))
    try:
        assert list(te.fused.values())[0].chain_id == cid
        assert te._precompile_thread is not None
        te._precompile_thread.join(timeout=60)
        assert not te._precompile_thread.is_alive()
        warmed = _op_counter("scanner_tpu_op_precompile_seconds")
        assert cid in warmed
        fki = list(te.fused.values())[0]
        assert fki._warm_state == "done"
        # members were never scheduled for individual warm-up
        for ki in te.kernels.values():
            assert ki._warm_state == "idle", ki.node.name
    finally:
        te.close()


def test_fusion_metrics_series_present(sc):
    """The fusion gauges register under their catalogued names and the
    planner sets the chains-planned gauge per chain id."""
    snap = registry().snapshot()
    for name in fusion.FUSION_SERIES:
        assert name in snap, name
    chains = {s["labels"]["chain"]: s["value"]
              for s in snap.get("scanner_tpu_fusion_chains_planned",
                                {}).get("samples", [])}
    assert chains.get("Resize+Blur+Histogram") == 3


# ---------------------------------------------------------------------------
# sharded gangs (slow): fused chains with a composed-stencil halo
# ---------------------------------------------------------------------------

@pytest.mark.slow
def test_fused_equivalence_gang_sharded(tmp_path):
    """A fused chain with a stencil HEAD (OpticalFlow+Blur, composed
    windows [2, 0] -> 1 halo row) runs sharded over a real 2-worker
    gang: the composed-stencil back-reach past the shard boundary rides
    the halo exchange, the output is bit-exact vs single-host, and it
    stays bit-exact after the gang re-forms around a replaced worker."""
    from scanner_tpu.engine import gang as egang
    from scanner_tpu.engine.service import Master, Worker
    from scanner_tpu.util import metrics as _mx

    def halo():
        entry = _mx.registry().snapshot().get(
            "scanner_tpu_gang_shard_halo_bytes_total", {})
        return sum(s["value"] for s in entry.get("samples", []))

    def build(s):
        f = s.io.Input([NamedVideoStream(s, "fzg")])
        flow = s.ops.OpticalFlow(frame=f)
        return s.ops.Blur(frame=flow, kernel_size=3, sigma=1.1)

    def run_one(client, name, **perf_kw):
        out = NamedStream(client, name)
        client.run(client.io.Output(build(client), [out]),
                   PerfParams.manual(4, 8, **perf_kw),
                   cache_mode=CacheMode.Overwrite, show_progress=False)
        return _load(out)

    db_path = str(tmp_path / "db")
    vid = str(tmp_path / "v.mp4")
    scv.synthesize_video(vid, num_frames=16, width=W, height=H,
                         fps=24, keyint=8)
    seed = Client(db_path=db_path)
    seed.ingest_videos([("fzg", vid)])
    single = run_one(seed, "fzg_single")

    m = Master(db_path=db_path, no_workers_timeout=60.0)
    addr = f"localhost:{m.port}"
    old_t = egang.form_timeout_s()
    egang.set_form_timeout_s(6.0)
    workers = [Worker(addr, db_path=db_path) for _ in range(2)]
    sc2 = Client(db_path=db_path, master=addr)
    try:
        h0 = halo()
        sharded = run_one(sc2, "fzg_shard", gang_hosts=2)
        assert halo() - h0 > 0, \
            "composed-stencil shard back-reach must ride the halo"
        # re-form: replace one member, run the same fused graph again
        workers[0].stop()
        workers[0] = Worker(addr, db_path=db_path)
        reformed = run_one(sc2, "fzg_reform", gang_hosts=2)
    finally:
        sc2.stop()
        for w in workers:
            w.stop()
        m.stop()
        egang.set_form_timeout_s(old_t)
        seed.stop()
    _assert_rows_equal(single, sharded)
    _assert_rows_equal(single, reformed)
