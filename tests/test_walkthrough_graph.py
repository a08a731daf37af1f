"""Upstream's walkthrough graph, Input -> Stride 2 -> Resize -> Grayscale
(the two one fused device program) -> the user's Python op
`CloneChannels` (host) -> Output, through `Client.run`, 128x96 -> 64x48:
against the benchmark's plain reference (benchmark/reference/
Walkthrough.py) before the encoder; fused against staged; the device
`Grayscale` against its host flavour; the device column handed to the
host op (`evaluate:handoff`, its counters, contiguous rows); the kept
evaluator adopted on a second run; the fuse decision made once a graph;
the spans a cell reads; and the reference's controls, each failing its
own number.
"""

import json
import os
import sys
from types import SimpleNamespace

import numpy as np
import pytest

from scanner_tpu import (CacheMode, Client, FrameType, NamedStream,
                         PerfParams, register_op)
import scanner_tpu.kernels  # noqa: F401  (registers the stdlib ops)
from scanner_tpu import video as scv
from scanner_tpu.engine import batch as _batch
from scanner_tpu.engine import evaluate as ev
from scanner_tpu.engine import framecache as fc
from scanner_tpu.graph import fusion
from scanner_tpu.kernels import imgproc
from scanner_tpu.util.metrics import registry
from scanner_tpu.util.profiler import Profiler

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
H, W, OH, OW, N_FRAMES, KEYINT, STRIDE = 96, 128, 48, 64, 70, 16, 2
CHAIN = "Resize+Grayscale"


@register_op()
def WalkBytes(config, frame: FrameType) -> bytes:
    """A frame as its bytes: a column that no codec touches (an H.264
    column is YUV420 at studio swing, lossless or not)."""
    f = np.ascontiguousarray(frame)
    assert f.shape == (OH, OW, 3) and f.dtype == np.uint8
    return f.tobytes()


@pytest.fixture(scope="module")
def bench():
    """The benchmark's clip generator, the walkthrough's builder (it
    registers `CloneChannels`) and its reference, by their own names."""
    sys.path.insert(0, os.path.join(REPO, "benchmark"))
    try:
        import clipgen
        from graphs import walkthrough
        from reference import Walkthrough
        yield SimpleNamespace(clipgen=clipgen, builder=walkthrough,
                              ref=Walkthrough)
    finally:
        sys.path.remove(os.path.join(REPO, "benchmark"))


@pytest.fixture(scope="module")
def cfg():
    """The configuration as the cell states it, at the test's sizes."""
    with open(os.path.join(REPO, "benchmark", "configs",
                           "walkthrough_1080p.json")) as f:
        c = json.load(f)
    c["video"].update(height=H, width=W, frames=N_FRAMES, keyint=KEYINT)
    c["output"].update(height=OH, width=OW)
    c["graph"]["ops"][0]["stream_args"] = {"width": OW, "height": OH}
    return c


@pytest.fixture(scope="module")
def clip(tmp_path_factory, bench):
    path = str(tmp_path_factory.mktemp("walk") / "clip.mp4")
    bench.clipgen.encode_clip(path, 13, N_FRAMES, H, W, 24, KEYINT)
    return path


@pytest.fixture()
def sc(tmp_path, monkeypatch, clip):
    """A client on the accelerator path of the CPU mesh: device staging,
    the YUV420 wire converted on the device (what the reference reads),
    the frame cache."""
    monkeypatch.setenv("SCANNER_TPU_KERNEL_DEVICES", "all")
    monkeypatch.setenv("SCANNER_TPU_YUV_DEVICE", "force")
    was = fc.enabled()
    fc.set_enabled(True)
    client = Client(db_path=str(tmp_path / "db"))
    client.ingest_videos([("movie", clip)])
    yield client
    client.stop()
    fc.set_enabled(was)
    fc.cache().clear()


def _counter(series, **labels):
    return sum(s["value"]
               for s in registry().snapshot().get(series, {"samples": []})
               ["samples"]
               if all(s["labels"].get(k) == v for k, v in labels.items()))


def _wire(sc, rows):
    auto = scv.open_automata(sc._db, "movie", output_format="yuv420")
    try:
        return list(np.asarray(auto.get_frames(list(rows))))
    finally:
        auto.close()


def _run(sc, bench, cfg, name, rows=None, as_bytes=True, fused=True):
    """The cell's graph over every `STRIDE`-th row (the first `rows`
    output rows of them), its frames committed as their bytes or, with
    `as_bytes` off, as the cell commits them: an H.264 column.  Returns
    (the job, the committed frames, their source rows)."""
    source = range(0, N_FRAMES, STRIDE)[:rows]
    request = [{"table": "movie", "rows": source,
                "sampler": "Stride" if rows is None else "Gather"}]
    fusion.set_enabled(fused)
    try:
        node = bench.builder.build(sc, request, cfg["graph"], {})
        out = NamedStream(sc, name)
        job = sc.run(sc.io.Output(sc.ops.WalkBytes(frame=node) if as_bytes
                                  else node, [out]),
                     PerfParams.manual(8, 16),
                     cache_mode=CacheMode.Overwrite, show_progress=False)
    finally:
        fusion.set_enabled(True)
    got = [np.frombuffer(f, np.uint8).reshape(OH, OW, 3) if as_bytes
           else np.asarray(f) for f in out.load()]
    return job, got, source


@pytest.mark.parametrize("fused", [True, False], ids=["fused", "staged"])
def test_the_program_is_the_references_equations_before_the_encoder(
        sc, bench, cfg, fused):
    """The reference adds the same float32 taps in the same order, and
    the luma is integer arithmetic on every flavour: what is left is a
    backend's contraction of a multiply into an add (XLA's CPU backend
    does), which moves a sum at a rounding's edge to the other side: a
    counted share of the pixels, each one level off (18 of 107,520
    here, fused and staged alike)."""
    rows0 = _counter("scanner_tpu_op_rows_total", op=CHAIN)
    _, got, source = _run(sc, bench, cfg, f"eq_{fused}", fused=fused)
    assert len(got) == len(source) == N_FRAMES // STRIDE
    want = [bench.ref.expected(f, cfg) for f in _wire(sc, source)]
    assert all(f.shape == (OH, OW, 3) and f.dtype == np.uint8 for f in got)
    gaps = [np.abs(a.astype(np.int16) - b) for a, b in zip(got, want)]
    assert max(int(g.max()) for g in gaps) <= 1
    assert sum(int(g.sum()) for g in gaps) <= 3e-4 * len(got) * OH * OW * 3
    assert all(np.array_equal(f[..., 0], f[..., 1])
               and np.array_equal(f[..., 1], f[..., 2]) for f in got)
    ran_fused = _counter("scanner_tpu_op_rows_total", op=CHAIN) - rows0
    assert ran_fused == (len(source) if fused else 0)


@pytest.mark.parametrize("rows", [3, 16, 21, None],
                         ids=["3", "16", "21", "ragged_tail"])
def test_fused_equals_staged_bit_for_bit(sc, bench, cfg, rows):
    """Under a rung, a whole packet, a packet and a tail, and the whole
    strided stream (35 rows: two 16-row tasks and 3)."""
    _, fused, _ = _run(sc, bench, cfg, f"ab_f{rows}", rows)
    _, staged, _ = _run(sc, bench, cfg, f"ab_s{rows}", rows, fused=False)
    assert len(fused) == len(staged) == (rows or N_FRAMES // STRIDE)
    assert all(np.array_equal(a, b) for a, b in zip(fused, staged))


def test_device_grayscale_is_the_host_flavour_on_every_colour():
    """All 2**24 colours through the numpy flavour, the jitted one and
    the stated integers; and a grey pixel stays what it is."""
    v = np.arange(256, dtype=np.uint8)
    rgb = np.stack(np.meshgrid(v, v, v, indexing="ij"), -1) \
        .reshape(16, 1024, 1024, 3)
    host = imgproc.gray3(rgb)
    device = np.asarray(imgproc._gray3_impl(rgb))
    r, g, b = (rgb[..., c].astype(np.int64) for c in range(3))
    stated = ((19595 * r + 38470 * g + 7471 * b) >> 16).astype(np.uint8)
    assert np.array_equal(host, device)
    assert all(np.array_equal(host[..., c], stated) for c in range(3))
    grey = np.repeat(v[:, None], 3, 1)
    assert np.array_equal(imgproc.gray3(grey)[:, 0], v)


def test_grayscale_is_a_device_op_that_fuses_behind_resize(sc, bench, cfg):
    from scanner_tpu import DeviceType
    from scanner_tpu.graph import ops as O
    spec = O.registry.get("Grayscale")
    assert spec.device == DeviceType.TPU and spec.batch == 16
    _run(sc, bench, cfg, "plan", 16)
    chains = [f for t in ev.live_evaluators() for f in t.fused.values()]
    assert chains and all(f.member_names == ["Resize", "Grayscale"]
                          for f in chains)
    assert _counter("scanner_tpu_fusion_chains_planned", chain=CHAIN) == 2


def test_the_handed_off_column_reaches_the_host_op_as_contiguous_views(
        sc, bench, cfg, monkeypatch):
    """The chain's frame column is started on its way when the chain's
    call is dispatched (`prefetch_host` on a device batch, once a
    chunk), the host op's rows are C-contiguous views of one array, and
    the span and its three counters say so."""
    started, seen = [], []
    real_prefetch = _batch.ColumnBatch.prefetch_host
    monkeypatch.setattr(
        _batch.ColumnBatch, "prefetch_host",
        lambda self: (started.append(_batch._is_jax(self.data)),
                      real_prefetch(self))[1])
    real_hand_off = ev.TaskEvaluator._hand_off

    def hand_off(self, op, b):
        out = real_hand_off(self, op, b)
        seen.append((op, out.data, [out.element_at(i)
                                    for i in range(len(out))]))
        return out

    monkeypatch.setattr(ev.TaskEvaluator, "_hand_off", hand_off)
    before = {k: _counter(f"scanner_tpu_op_handoff_{k}_total",
                          op="CloneChannels")
              for k in ("seconds", "bytes", "rows")}
    job, got, source = _run(sc, bench, cfg, "handoff", as_bytes=False)
    n = len(source)
    assert [op for op, _, _ in seen] == ["CloneChannels"] * 5  # 8 8 8 8 3
    assert started.count(True) == 5
    for _, data, rows in seen:
        assert isinstance(data, np.ndarray) and data.flags.c_contiguous
        assert all(r.flags.c_contiguous and np.shares_memory(r, data)
                   and r.shape == (OH, OW, 3) for r in rows)
    after = {k: _counter(f"scanner_tpu_op_handoff_{k}_total",
                         op="CloneChannels")
             for k in ("seconds", "bytes", "rows")}
    assert after["rows"] - before["rows"] == n
    assert after["bytes"] - before["bytes"] == n * OH * OW * 3
    assert after["seconds"] > before["seconds"]
    # the CPU backend holds every batch row-major: nothing to lay out
    assert _counter("scanner_tpu_op_handoff_rows_total", op="CloneChannels",
                    layout="relaid") == 0
    ivs = [iv for p in sc.get_profile(job).profilers
           for iv in p.intervals()]
    hand = [iv for iv in ivs if iv.name == "evaluate:handoff"]
    inputs = [iv for iv in ivs if iv.name == "evaluate:inputs"
              and iv.args["op"] == "CloneChannels"]
    assert len(hand) == len(inputs) == 5
    assert sum(iv.args["rows"] for iv in hand) == n
    assert all(iv.args == {"op": "CloneChannels", "rows": iv.args["rows"],
                           "layout": "asis"} for iv in hand)
    assert all(any(o.start <= iv.start and iv.end <= o.end for o in inputs)
               for iv in hand)


def test_a_planar_column_is_laid_out_row_major_for_the_host_op():
    """What the chip does with a frame column, driven here with a real
    planar array: `relaid`, by the sink's own program."""
    import jax
    from jax.experimental.layout import Format, Layout
    host = np.random.default_rng(3).integers(
        0, 255, (6, 12, 16, 3)).astype(np.uint8)
    planar = jax.device_put(host, Format(
        Layout(major_to_minor=(0, 3, 1, 2)),
        jax.sharding.SingleDeviceSharding(jax.devices()[0])))
    assert not np.asarray(planar).flags.c_contiguous
    te = SimpleNamespace(profiler=Profiler(0, level=1))
    rows0 = _counter("scanner_tpu_op_handoff_rows_total", op="AnOp",
                     layout="relaid")
    b = _batch.ColumnBatch(np.arange(6), planar).prefetch_host()
    out = ev.TaskEvaluator._hand_off(te, "AnOp", b)
    assert out.data.flags.c_contiguous and np.array_equal(out.data, host)
    assert all(np.shares_memory(out.element_at(i), out.data)
               for i in range(6))
    assert _counter("scanner_tpu_op_handoff_rows_total", op="AnOp",
                    layout="relaid") == rows0 + 6
    (iv,) = [iv for iv in te.profiler.intervals()
             if iv.name == "evaluate:handoff"]
    assert iv.args == {"op": "AnOp", "rows": 6, "layout": "relaid"}


def test_the_kept_evaluator_is_adopted_on_a_second_run(sc, bench, cfg):
    reuses = _counter("scanner_tpu_evaluator_reuses_total")
    setups = _counter("scanner_tpu_evaluator_setups_total")
    rows = _counter("scanner_tpu_op_rows_total", op=CHAIN)
    _, first, _ = _run(sc, bench, cfg, "keep0", 16)
    kept = [t for t in ev.live_evaluators() if t.fused]
    # another sampler and other rows are no other graph
    _, second, source = _run(sc, bench, cfg, "keep1")
    again = [t for t in ev.live_evaluators() if t.fused]
    assert kept and {id(t) for t in again} == {id(t) for t in kept}
    assert _counter("scanner_tpu_evaluator_setups_total") \
        == setups + 2 * len(kept)
    assert _counter("scanner_tpu_evaluator_reuses_total") \
        == reuses + len(kept)
    assert all(m in t.info.ops for t in kept for f in t.fused.values()
               for m in f.chain.members)
    assert _counter("scanner_tpu_op_rows_total", op=CHAIN) \
        == rows + 16 + len(source)
    assert all(np.array_equal(a, b) for a, b in zip(first, second))


def test_the_fuse_decision_is_made_once_a_graph(sc, bench, cfg,
                                                monkeypatch):
    """The ledger judging every member compute-bound after a graph's
    first evaluator was made does not take the graph's later evaluators
    off the chain; forgotten, the decision is made anew."""
    rows = _counter("scanner_tpu_op_rows_total", op=CHAIN)
    _run(sc, bench, cfg, "once0", 8)
    monkeypatch.setattr(fusion, "_ledger_probe", lambda node: "compute")
    sc._evaluators.close()  # the next run makes its evaluator anew
    _run(sc, bench, cfg, "once1", 8)
    assert _counter("scanner_tpu_op_rows_total", op=CHAIN) == rows + 16
    fusion._DECIDED.clear()
    sc._evaluators.close()
    _run(sc, bench, cfg, "once2", 8)
    assert _counter("scanner_tpu_op_rows_total", op=CHAIN) == rows + 16
    fusion._DECIDED.clear()


def test_the_chains_span_keeps_its_children_and_the_host_op_its_own(
        sc, bench, cfg):
    job, _, source = _run(sc, bench, cfg, "spans", as_bytes=False)
    ivs = [iv for p in sc.get_profile(job).profilers
           for iv in p.intervals()]
    chain = [iv for iv in ivs if iv.name == "evaluate:" + CHAIN]
    assert len(chain) == 5
    assert sum(iv.args["rows"] for iv in chain) == len(source)
    for child in ("evaluate:dispatch", "evaluate:device_wait"):
        # every call is dispatched inside its chain's span and waited
        # for once: inside the span of the call two on, or, an
        # evaluator's last two in flight, at its release after its
        # last task
        kids = [iv for iv in ivs if iv.name == child
                and iv.args["op"] == CHAIN]
        assert len(kids) == 5
        late = [iv for iv in kids
                if not any(o.start <= iv.start and iv.end <= o.end
                           for o in chain)]
        assert not late or child == "evaluate:device_wait"
        for t in {iv.thread for iv in late}:
            mine = [iv for iv in late if iv.thread == t]
            assert len(mine) <= 2 and all(
                iv.start >= max(o.end for o in chain if o.thread == t)
                for iv in mine)
    host = [iv for iv in ivs if iv.name == "evaluate:CloneChannels"]
    assert len(host) == 5 and {iv.args["device"] for iv in host} == {"host"}
    assert not [iv for iv in ivs if iv.name in ("evaluate:Resize",
                                                "evaluate:Grayscale")]


def test_the_chains_program_names_the_chain_and_each_member():
    """The scopes the device trace reads: the chain's id outermost, a
    member's name inside it."""
    import jax
    from scanner_tpu import DeviceType
    from scanner_tpu.graph import ops as O
    members = []
    for name, args in (("Resize", {"width": OW, "height": OH}),
                       ("Grayscale", {})):
        spec = O.registry.get(name)
        k = O.registry.canonical_factory(spec)(
            O.KernelConfig(device=DeviceType.TPU, args=args), **args)
        members.append((name, k, 0))
    hlo = jax.jit(lambda y: ev._trace_chain(CHAIN, members, y)).lower(
        jax.ShapeDtypeStruct((8, H, W, 3), np.uint8)).as_text(
            debug_info=True)
    assert f"{CHAIN}/Resize/" in hlo and f"{CHAIN}/Grayscale/" in hlo


CONTROL_FAILS_BY = {"bf16": "bf16_pattern_share",
                    "nearest": "psnr_under_floor_db",
                    "no_gray": "gray_channel_spread"}


@pytest.fixture(scope="module")
def control_sample(tmp_path_factory, bench, cfg):
    """Two whole items of the column at 384x216 -> 128x96 (the cell's
    own scales): the wires of 64 output rows, and the configuration with
    this size's floor (39.3 dB a right run, 34-35 without antialiasing);
    the other limits are the cell's own."""
    import copy
    c = copy.deepcopy(cfg)
    c["video"].update(height=216, width=384, frames=128, keyint=32)
    c["output"].update(height=96, width=128, psnr_floor_db=37.4)
    root = tmp_path_factory.mktemp("walk_controls")
    path = str(root / "clip.mp4")
    bench.clipgen.encode_clip(path, 5, 128, 216, 384, 30, 32)
    with Client(db_path=str(root / "db")) as client:
        client.ingest_videos([("movie", path)])
        wires = _wire(client, range(0, 128, STRIDE))
    return c, wires


@pytest.mark.parametrize("control", sorted(CONTROL_FAILS_BY))
def test_each_control_fails_its_own_number(bench, control_sample, control):
    c, wires = control_sample
    values = bench.ref.compare(c, wires, [None] * len(wires),
                               control=control)
    over = [k for k, limit in bench.ref.LIMITS.items() if values[k] > limit]
    assert over == [CONTROL_FAILS_BY[control]], values


def test_the_references_own_round_trip_reads_nothing(bench, control_sample):
    c, wires = control_sample
    frames = [bench.ref.expected(f, c) for f in wires]
    items = [f for lo in (0, 32)
             for f in bench.ref.round_trip(frames[lo:lo + 32], c)]
    values = bench.ref.compare(c, wires, items)
    assert values["psnr_deficit_db"] == 0.0
    assert -3.0 < values["psnr_under_floor_db"] < -1.0
    assert all(values[k] == 0 for k in ("frame_shape_errors",
                                        "out_frame_id_errors",
                                        "gray_channel_spread"))
    # a column one row late shows the wrong frames
    late = bench.ref.compare(c, wires[1:], items[:-1])
    assert late["out_frame_id_errors"] == len(wires) - 1
