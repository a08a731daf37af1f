"""The optical-flow graph, Input -> OpticalFlow (device, stencil [-1, 0]
over the table's frames) -> Range -> Output, a float32 frame column,
through `Client.run` against the benchmark's plain reference
(benchmark/reference/OpticalFlow.py) under its own limits, over task
and packet boundaries and from ranges that start on and off row 0; the
reference's bfloat16 control; and the spans and counters of the window's
gather and of the raw column's write.
"""

import os
import sys

import numpy as np
import pytest

from scanner_tpu import (CacheMode, Client, NamedStream, NamedVideoStream,
                         PerfParams)
import scanner_tpu.kernels  # noqa: F401  (registers the stdlib ops)
from scanner_tpu import video as scv
from scanner_tpu.engine import framecache as fc
from scanner_tpu.util.metrics import registry

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
H, W, N_FRAMES, KEYINT = 96, 128, 64, 16
CFG = {"video": {"height": H, "width": W}}
RANGES = {"from_row_0": (0, 40), "off_a_keyframe": (24, 56),
          "on_a_keyframe": (32, 64)}


@pytest.fixture(scope="module")
def bench():
    """The benchmark's clip generator and flow reference, by their own
    names (they import each other so)."""
    sys.path.insert(0, os.path.join(REPO, "benchmark"))
    try:
        import clipgen
        from reference import OpticalFlow
        yield clipgen, OpticalFlow
    finally:
        sys.path.remove(os.path.join(REPO, "benchmark"))


@pytest.fixture(scope="module")
def clip(tmp_path_factory, bench):
    path = str(tmp_path_factory.mktemp("flow") / "clip.mp4")
    bench[0].encode_clip(path, 11, N_FRAMES, H, W, 24, KEYINT)
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
    # the pool is a process singleton: leave no page for the next file
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


def _run(sc, name, span, perf):
    """`Range(OpticalFlow(frame), span)` of the movie; returns (job, the
    source rows, the committed fields)."""
    flow = sc.ops.OpticalFlow(
        frame=sc.io.Input([NamedVideoStream(sc, "movie")]))
    out = NamedStream(sc, name)
    job = sc.run(sc.io.Output(sc.streams.Range(flow, [span]), [out]), perf,
                 cache_mode=CacheMode.Overwrite, show_progress=False)
    return job, list(range(*span)), [np.asarray(x) for x in out.load()]


def _handed(sc, rows):
    """What the benchmark's harness hands `compare` for one sampled run:
    its rows' wires and the wire of the row before it."""
    halo = [rows[0] - 1] if rows[0] else []
    return _wire(sc, rows), [dict(zip(halo, _wire(sc, halo)))]


@pytest.mark.parametrize("perf", [(8, 16), (16, 16), (4, 32)],
                         ids=["streamed", "whole_task", "eight_packets"])
@pytest.mark.parametrize("span", sorted(RANGES))
def test_flow_graph_agrees_with_the_reference_on_every_row(
        sc, bench, span, perf):
    """A 64-row table in tasks of 16 or 32 rows and packets of 4, 8 or
    16: every committed field, each task's and packet's first among
    them, stands within the reference's limits; nothing goes uncompared
    once the row before the range is handed over."""
    _, R = bench
    _, rows, got = _run(sc, f"flow_{span}", RANGES[span],
                        PerfParams.manual(*perf))
    assert len(got) == len(rows)
    wires, halo = _handed(sc, rows)
    values = R.compare(CFG, wires, got, rows=[rows], window_wires=halo)
    assert set(values) == set(R.LIMITS)
    assert all(values[k] <= R.LIMITS[k] for k in R.LIMITS), values
    # there is motion to find: the fields are no zeros
    assert max(float(np.abs(g).max()) for g in got[1:]) > 0.05


@pytest.mark.parametrize("span", sorted(RANGES))
def test_the_window_lies_over_the_table(sc, bench, span):
    """Output row i of Range(a, b) is the flow from source row a + i - 1
    to a + i: the first row of a range past row 0 is the flow from the
    row before the range, which the range does not hold; table row 0
    repeats itself and reads exactly 0."""
    _, R = bench
    a, b = RANGES[span]
    _, rows, got = _run(sc, f"table_{span}", (a, b), PerfParams.estimate())
    assert all(g.shape == (H, W, 2) and g.dtype == np.float32 for g in got)
    if a == 0:
        assert not got[0].any() and got[1].any()
        return
    before, first = (R.wire.to_rgb(f, H, W) for f in _wire(sc, [a - 1, a]))
    want = R.solver(None)(before, first)
    assert np.abs(want).max() > 0.05
    assert np.abs(got[0] - want).max() <= R.LIMITS["flow_gap"] \
        * max(1.0, np.abs(want).max())
    # with its predecessor withheld the comparison says so
    assert R.compare(CFG, _wire(sc, rows), got, rows=[rows])[
        "flow_rows_uncompared"] == 1


@pytest.mark.parametrize("span", sorted(RANGES))
def test_the_bf16_control_fails_by_the_gap_alone(sc, bench, span):
    """The reference with its average's operands in bfloat16, in the
    program's place: over the limit on `flow_gap`, and on nothing else."""
    _, R = bench
    rows = list(range(*RANGES[span]))
    wires, halo = _handed(sc, rows)
    values = R.compare(CFG, wires, [None] * len(rows), control=R.CONTROL,
                       rows=[rows], window_wires=halo)
    assert values["flow_gap"] > 3 * R.LIMITS["flow_gap"], values
    assert all(values[k] <= R.LIMITS[k] for k in R.LIMITS
               if k != "flow_gap"), values


@pytest.mark.parametrize("fault,number", [
    ("short", "flow_shape_errors"), ("float64", "flow_shape_errors"),
    ("row0", "flow_row0_nonzero"), ("late", "flow_gap")])
def test_a_broken_column_is_not_correct(sc, bench, fault, number):
    _, R = bench
    _, rows, got = _run(sc, f"fault_{fault}", (0, 16),
                        PerfParams.estimate())
    if fault == "short":
        got[3] = got[3][:-1]
    elif fault == "float64":
        got[3] = got[3].astype(np.float64)
    elif fault == "row0":
        got[0] = got[0] + np.float32(1e-6)
    else:  # every field one row late
        got = got[:1] + got[:-1]
    values = R.compare(CFG, _wire(sc, rows), got, rows=[rows],
                       window_wires=[{}])
    assert values[number] > R.LIMITS[number], values


def test_without_rows_the_wires_are_runs_by_their_barcodes(sc, bench):
    """`control_on_chip.py` hands neither `rows` nor `window_wires`: the
    runs are read off the barcodes, and a run's first row past row 0
    goes uncompared."""
    _, R = bench
    _, rows_a, got_a = _run(sc, "runs_a", (0, 8), PerfParams.estimate())
    _, rows_b, got_b = _run(sc, "runs_b", (40, 48), PerfParams.estimate())
    values = R.compare(CFG, _wire(sc, rows_a + rows_b), got_a + got_b)
    assert values["flow_rows_uncompared"] == 1
    assert values["flow_gap"] <= R.LIMITS["flow_gap"]


@pytest.mark.parametrize("perf,calls", [((16, 16), 12), ((8, 16), 12),
                                        ((32, 32), 12)],
                         ids=["tasks", "packets_of_streamed_tasks",
                              "one_task"])
def test_gather_and_raw_spans_and_their_counters_move_together(
        sc, perf, calls):
    """`evaluate:gather` once a call of the op (four rows a call), inside
    `evaluate:OpticalFlow`; `save:raw` once a task, inside `save:write`;
    each with its counter at the same two clock reads, and the bytes
    beside them."""
    series = {
        "gather_s": ("scanner_tpu_stencil_gather_seconds_total",
                     {"op": "OpticalFlow"}),
        "gather_b": ("scanner_tpu_stencil_gather_bytes_total",
                     {"op": "OpticalFlow"}),
        "raw_s": ("scanner_tpu_raw_frame_seconds_total", {}),
        "raw_b": ("scanner_tpu_raw_frame_bytes_total", {})}
    before = {k: _counter(s, **lab) for k, (s, lab) in series.items()}
    job, rows, got = _run(sc, f"spans_{perf[0]}_{perf[1]}", (16, 64),
                          PerfParams.manual(*perf))
    moved = {k: _counter(s, **lab) - before[k]
             for k, (s, lab) in series.items()}
    by = {}
    for p in sc.get_profile(job).profilers:
        for iv in p.intervals():
            by.setdefault(iv.name, []).append(iv)
    tasks = len(rows) // perf[1] + (len(rows) % perf[1] > 0)
    assert len(by["evaluate:gather"]) == calls
    assert {iv.args["op"] for iv in by["evaluate:gather"]} == {"OpticalFlow"}
    assert sum(iv.args["rows"] for iv in by["evaluate:gather"]) == len(rows)
    assert len(by["save:raw"]) == tasks
    assert sum(iv.args["rows"] for iv in by["save:raw"]) == len(rows)
    for child, parent in (("evaluate:gather", "evaluate:OpticalFlow"),
                          ("save:raw", "save:write")):
        for c in by[child]:
            assert any(p.start <= c.start and c.end <= p.end
                       for p in by[parent]), child
    assert moved["gather_s"] == pytest.approx(
        sum(iv.end - iv.start for iv in by["evaluate:gather"]), abs=1e-6)
    assert moved["raw_s"] == pytest.approx(
        sum(iv.end - iv.start for iv in by["save:raw"]), abs=1e-6)
    # every row twice over, as uint8 RGB; every field, and its framing
    assert moved["gather_b"] == len(rows) * 2 * H * W * 3
    field = H * W * 2 * 4
    assert len(rows) * field < moved["raw_b"] < len(rows) * (field + 512)



def test_a_field_reads_back_the_same_from_planar_views_as_from_rows(
        sc, monkeypatch):
    """The raw branch of `_save_task` pickles a row whether it comes as
    a strided view of planes (what a fetch handed over before sink
    batches were laid out row-major on the device) or contiguous: both
    read back as equal float32 arrays of the row's shape, C-contiguous;
    the streams differ only in how the array is rebuilt."""
    from scanner_tpu.engine.batch import ColumnBatch
    real, handed = ColumnBatch.to_host, []

    def planar(self):
        got = real(self)
        if got is not self and got.data.dtype == np.float32:
            got.data = np.ascontiguousarray(
                got.data.transpose(0, 3, 1, 2)).transpose(0, 2, 3, 1)
            handed.append(got.data[0].flags.c_contiguous)
        return got

    _job, rows, rowwise = _run(sc, "rowwise", (16, 48),
                               PerfParams.manual(16, 16))
    monkeypatch.setattr(ColumnBatch, "to_host", planar)
    _job, _rows, planes = _run(sc, "planes", (16, 48),
                               PerfParams.manual(16, 16))
    assert handed == [False, False] and len(planes) == len(rows) == 32
    for a, b in zip(rowwise, planes):
        assert a.dtype == b.dtype == np.float32
        assert a.shape == b.shape == (H, W, 2)
        assert a.flags.c_contiguous and b.flags.c_contiguous
        np.testing.assert_array_equal(a, b)
    assert np.abs(np.stack(planes)).max() > 0


@pytest.mark.parametrize("yuv_wire,geometry,affinity", [
    (True, (40, 56, 12), "1"), (False, (40, 72, 12), "1"),
    (True, (40, 88, 12), "0")], ids=["wire", "rgb", "wire_on_one_chip"])
def test_the_warm_up_rehearses_the_window_at_every_chunk_length(
        sc, monkeypatch, yuv_wire, geometry, affinity):
    """A stencilled op's column comes in chunks of the work packet plus
    the window's reach, and of the work packet alone where a range
    starts at table row 0: which one a request meets depends on its
    rows, so the evaluator's warm-up compiles the conversion and the
    gather for both, at a geometry nothing else here has compiled.  A
    host with one chip assigns its one evaluator no device (jax's default
    placement): the warm-up runs there all the same."""
    from scanner_tpu.engine import evaluate as ev
    from scanner_tpu.graph import analysis as A
    from scanner_tpu.kernels.color import _device_converter
    from scanner_tpu.util.profiler import Profiler

    monkeypatch.setenv("SCANNER_TPU_PRECOMPILE", "1")
    monkeypatch.setenv("SCANNER_TPU_DEVICE_AFFINITY", affinity)
    h, w, wp = geometry
    flow = sc.ops.OpticalFlow(
        frame=sc.io.Input([NamedVideoStream(sc, "movie")]))
    info = A.analyze([sc.io.Output(flow, [NamedStream(sc, "warm_flow")])])
    before = ev._window_gatherer()._cache_size()
    te = ev.TaskEvaluator(info, Profiler(), precompile=(h, w, wp),
                          yuv_wire=yuv_wire)
    try:
        te._precompile_thread.join(timeout=120)
        assert not te._precompile_thread.is_alive()
        (ki,) = te.kernels.values()
        assert (ki.device is None) == (affinity == "0")
        assert ki.window_chunks == (13, 12) and ki.yuv_wire == yuv_wire
        # two chunk lengths at the op's one rung (four rows a call)
        assert ev._window_gatherer()._cache_size() - before == 2
        assert _device_converter(h, w)._cache_size() == 2 * yuv_wire
    finally:
        te.close()


@pytest.fixture(scope="module")
def one_v5e_chip():
    """One chip of a described (not attached) v5e, as the
    `on-chip-measurement` guide has it: described inside a fixture, in
    this one file, skipped where it cannot be."""
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — no TPU compiler here
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    return SingleDeviceSharding(topo.devices[0])


@pytest.mark.parametrize("program", ["luma", "solve"])
def test_the_ops_programs_hold_no_bfloat16_on_the_v5e(one_v5e_chip, program):
    """The configuration states float32 throughout: the op's two
    programs, compiled for the chip at the flow cell's packet (four
    1080p rows), name no bf16 in their optimised HLO.  (The averaging as
    a one-channel convolution at default precision did:
    `bf16[4,1,1080,1920]` operands.)  A compile is not a chip run."""
    import re
    import jax
    import jax.numpy as jnp
    from scanner_tpu.kernels.imgproc import _grayscale, _horn_schunck
    fn, shapes = {
        "luma": (_grayscale, [((4, 1080, 1920, 3), jnp.uint8)]),
        "solve": (_horn_schunck, [((4, 1080, 1920), jnp.float32)] * 2),
    }[program]
    args = [jax.ShapeDtypeStruct(dims, dtype, sharding=one_v5e_chip)
            for dims, dtype in shapes]
    hlo = jax.jit(fn).lower(*args).compile().as_text()
    assert "f32[4,1080,1920" in hlo
    assert not [ln for ln in hlo.splitlines() if re.search(r"\bbf16\b", ln)]


@pytest.mark.parametrize("rows", [16, 17])
def test_the_wire_conversion_holds_nothing_wider_than_uint8_in_hbm(
        one_v5e_chip, rows):
    """The wire conversion compiled for the chip at a 1080p packet, and
    at the flow cell's chunk of a packet and its halo row: the kernel is
    there (not its interpreter), no full-frame int32 plane, no
    temporaries to speak of (0.65-0.80 GB before PR 35), and after the
    kernel only bitcasts: the planes it writes are the device's layout
    of (rows, 1080, 1920, 3) uint8, which the Histogram kernel reads as
    it stands.  A compile is not a chip run.  (Here and not beside the
    converter's other tests: one file holds the described chip.)"""
    import re
    import jax
    import jax.numpy as jnp
    from scanner_tpu.kernels.color import _device_converter
    flat = jax.ShapeDtypeStruct((rows, 1080 * 1920 * 3 // 2), jnp.uint8,
                                sharding=one_v5e_chip)
    compiled = _device_converter(1080, 1920).lower(flat).compile()
    hlo = compiled.as_text()
    assert f"s32[{rows},1080,1920" not in hlo
    assert compiled.memory_analysis().temp_size_in_bytes < 0.1e9
    entry = hlo[hlo.index("ENTRY "):].splitlines()
    (kernel,) = [i for i, ln in enumerate(entry) if "tpu_custom_call" in ln]
    after = [re.search(r" ([a-z][a-z-]*)\(", ln).group(1)
             for ln in entry[kernel + 1:] if " = " in ln]
    assert after and set(after) == {"bitcast"}, after
    assert f"u8[{rows},1080,1920,3]" in entry[0]


def test_the_walkthroughs_chain_compiles_for_the_v5e_at_a_1080p_packet(
        one_v5e_chip):
    """`Resize+Grayscale` as `FusedKernelInstance` traces it, compiled
    for the chip at the walkthrough cell's packet (sixteen 1080p rows ->
    640x480): the chain's and each member's scope are on its operations,
    the 1080p input is never held as float32 (398 MB a packet: the
    h-pass converts after its gathers), what it does hold between the
    two passes is the 480-row float32 image and the taps' uint8 gathers
    (0.40 GB in all), and its result is the uint8 frame the host op is
    handed.  A compile is not a chip run.  (Here and not in
    tests/test_walkthrough_graph.py: one file holds the described
    chip.)"""
    import jax
    import jax.numpy as jnp
    from scanner_tpu import DeviceType
    from scanner_tpu.engine.evaluate import _trace_chain
    from scanner_tpu.graph import ops as O
    members = []
    for name, args in (("Resize", {"width": 640, "height": 480}),
                       ("Grayscale", {})):
        kernel = O.registry.canonical_factory(O.registry.get(name))(
            O.KernelConfig(device=DeviceType.TPU, args=args), **args)
        members.append((name, kernel, 0))
    packet = jax.ShapeDtypeStruct((16, 1080, 1920, 3), jnp.uint8,
                                  sharding=one_v5e_chip)
    compiled = jax.jit(lambda y: _trace_chain(
        "Resize+Grayscale", members, y)).lower(packet).compile()
    hlo = compiled.as_text()
    assert "Resize+Grayscale/Resize/" in hlo
    assert "Resize+Grayscale/Grayscale/" in hlo
    assert "f32[16,1080,1920" not in hlo
    assert compiled.memory_analysis().temp_size_in_bytes < 0.45e9
    entry = hlo[hlo.index("ENTRY "):].splitlines()[0]
    assert "u8[16,1080,1920,3]" in entry and "-> u8[16,480,640,3]" in entry
