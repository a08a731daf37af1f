"""One run of one cell, after the look for a chip: set-up, the measured
window over `Client.run`, the reduction to metrics, and the comparison
that decides `correct`.

Everything that belongs to one cell is data found by name: the
configuration (configs/<config>.json) with its graph of ops, the traffic
(traffic/<traffic>.json, read by the one generator trafficgen.py), each
per-layer metric (metrics/<name>.json and its reducer
reducers/<reducer>.py), the work functions (work/<name>.py) and the plain
reference of the configuration's graph (reference/<name>.py).  From the program it takes the public client, its
counters and its profiler intervals.
"""

import importlib
import json
import os
import shutil
import subprocess
import sys
import tempfile
import time

import numpy as np

import clipgen
import trace_reduce
import trafficgen
from reference import wire as wire_ref

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)

# stands for "never" and "nothing" where a number has to be printed
NEVER = 1e30


def log(msg):
    print(f"[bench] {msg}", file=sys.stderr, flush=True)


def load_json(*parts):
    with open(os.path.join(HERE, *parts)) as f:
        return json.load(f)


def merge(base, over):
    """`over` laid over `base`, dict by dict (tests shrink a cell so)."""
    out = dict(base)
    for k, v in (over or {}).items():
        out[k] = merge(base[k], v) if isinstance(v, dict) \
            and isinstance(base.get(k), dict) else v
    return out


def find_cell(manifest, name):
    for cell in manifest["workloads"]:
        if cell["name"] == name:
            return cell
    raise SystemExit(f"no workload {name!r} in BENCHMARK.json")


def load_cell(manifest, name, overrides=None):
    """(workloads entry, configuration, traffic) of a cell; `overrides`
    shrinks it for a CPU test."""
    spec = find_cell(manifest, name)
    entry = next(c for c in manifest["configs"]
                 if c["name"] == spec["config"])
    with open(os.path.join(ROOT, entry["file"])) as f:
        cfg = merge(json.load(f), (overrides or {}).get("config"))
    traffic = merge(load_json("traffic", spec["traffic"] + ".json"),
                    (overrides or {}).get("traffic"))
    return spec, cfg, traffic


def cell_metrics(manifest, group, cell):
    return [m for m in manifest[group]
            if cell in m.get("workloads", [cell])]


def build_native():
    """The .so is git-ignored and a copied tree keeps no mtimes to
    trust: always rebuild, before the first scanner_tpu.video import."""
    subprocess.run(["make", "-B", "-C", os.path.join(ROOT, "cpp")],
                   check=True, stdout=subprocess.DEVNULL)


class Counters:
    """Sums over the program's metric snapshot (`Client.metrics()`)."""

    def __init__(self, sc):
        self.snap = sc.metrics()

    def total(self, series, labels=None, not_labels=None):
        out = 0.0
        for s in self.snap.get(series, {}).get("samples", []):
            lab = s.get("labels", {})
            if all(lab.get(k) == v for k, v in (labels or {}).items()) \
                    and not any(lab.get(k) == v
                                for k, v in (not_labels or {}).items()):
                out += s.get("value", 0.0)
        return out


REAL_COMPILE = ("scanner_tpu_compile_total", None, {"cache": "hit"})
# stderr only: what the frame cache and the health layer did, for the log
DIAGNOSTICS = ("scanner_tpu_framecache_live_bytes",
               "scanner_tpu_framecache_capacity_bytes",
               "scanner_tpu_framecache_hits_total",
               "scanner_tpu_framecache_misses_total",
               "scanner_tpu_framecache_inserts_total",
               "scanner_tpu_framecache_evictions_total",
               "scanner_tpu_framecache_pressure_shrinks_total",
               "scanner_tpu_decoded_frames_total",
               "scanner_tpu_decode_seconds_total",
               "scanner_tpu_alerts_transitions_total")


def diagnostics(when, counters):
    log(when + ": " + ", ".join(
        f"{s.replace('scanner_tpu_', '')} {counters.total(s):.0f}"
        for s in DIAGNOSTICS))


class Cell:
    """The system under test, set up for one cell."""

    def __init__(self, cfg, traffic, seed, chips, workdir):
        import scanner_tpu.kernels  # noqa: F401 — registers the ops
        import scanner_tpu.models  # noqa: F401
        from scanner_tpu import Client

        self.cfg, self.traffic, self.seed = cfg, traffic, int(seed)
        v = cfg["video"]
        clip = os.path.join(workdir, "clip.mp4")
        t = time.time()
        clipgen.encode_clip(clip, seed, v["frames"], v["height"], v["width"],
                            v["fps"], v["keyint"])
        log(f"clip {v['frames']} x {v['width']}x{v['height']}: "
            f"{os.path.getsize(clip)} B in {time.time() - t:.1f} s")
        conf = os.path.join(workdir, "client.toml")
        with open(conf, "w") as f:
            for section, values in cfg["client"].items():
                f.write(f"[{section}]\n")
                for k, val in values.items():
                    f.write(f"{k} = {json.dumps(val)}\n")
        self.db_path = os.path.join(workdir, "db")
        self.sc = Client(config_path=conf, db_path=self.db_path)
        # the graph is one op, or `ops` in a chain, each fed the last
        # one's output through its `input` argument
        graph = cfg["graph"]
        self.ops = graph.get("ops", [graph])
        self.reference = importlib.import_module(
            "reference." + graph.get("reference", self.ops[-1]["op"]))
        self.op_args = self.reference.make_op_args(cfg, seed, workdir)
        self.plan = trafficgen.plan(traffic, cfg, seed, chips)
        t = time.time()
        _, failed = self.sc.ingest_videos(
            [(name, clip) for name in self.plan["tables"]])
        if failed:
            raise RuntimeError(f"ingest failed: {failed}")
        log(f"ingest of {len(self.plan['tables'])} tables: "
            f"{time.time() - t:.1f} s")
        self.n_requests = 0

    def run(self, request):
        """One `Client.run` over `request`; returns its record.  A
        request that raises is recorded as failed, not raised."""
        from scanner_tpu import (CacheMode, NamedStream, NamedVideoStream,
                                 PerfParams)
        sc = self.sc
        tag = f"out_{self.n_requests:05d}"
        self.n_requests += 1
        names = [f"{tag}_{j}" for j in range(len(request))]
        node = sc.io.Input([NamedVideoStream(sc, s["table"])
                            for s in request])
        sampler = request[0]["sampler"]
        if sampler != "All":
            node = getattr(sc.streams, sampler)(
                node, [trafficgen.sampler_args(s) for s in request])
        for op in self.ops:
            node = getattr(sc.ops, op["op"])(
                **{op.get("input", "frame"): node}, **op.get("args", {}),
                **self.op_args.get(op["op"], {}))
        out = sc.io.Output(node, [NamedStream(sc, n) for n in names])
        rec = {"request": request, "outputs": names, "job": None,
               "rows": sum(len(s["rows"]) for s in request),
               "t_call": time.time()}
        try:
            rec["job"] = sc.run(out, PerfParams.estimate(),
                                cache_mode=CacheMode.Overwrite,
                                show_progress=False)
        except Exception as e:  # noqa: BLE001 — counted, and reported
            log(f"request {tag} failed: {type(e).__name__}: {e}")
            rec["error"] = repr(e)
        rec["t_done"] = time.time()
        return rec

    def intervals(self, rec):
        """Program-profiler intervals of a request's job as
        (name, start, end) in host seconds."""
        if rec["job"] is None:
            return []
        return [(iv.name, iv.start, iv.end)
                for p in self.sc.get_profile(rec["job"]).profilers
                for iv in p.intervals()]

    def committed_rows(self, rec):
        """Rows of a finished request that its output tables hold."""
        tables = [self.sc.table(n) for n in rec["outputs"]
                  if self.sc.has_table(n)]
        return sum(t.num_rows() for t in tables if t.committed())

    def load(self, rec, j, rows):
        from scanner_tpu import NamedStream
        return [np.asarray(x) for x in
                NamedStream(self.sc, rec["outputs"][j]).load(rows=list(rows))]

    def wire(self, table, rows):
        """The flat I420 frames of `rows`, by a decode of its own through
        the program's video library on the host (the one H.264 decoder
        on the machine), apart from the engine's loader and cache."""
        from scanner_tpu import video as scv
        from scanner_tpu.storage import Database, make_storage
        db = Database(make_storage("posix", db_path=self.db_path))
        auto = scv.open_automata(db, table, output_format="yuv420")
        try:
            return np.asarray(auto.get_frames(list(rows)))
        finally:
            auto.close()


def check_sample(traffic, finished, rng):
    """What of the window to compare, drawn from the seed: `check.streams`
    streams of the finished requests, the longest among them, and of each
    a run of at most `check.rows` of its output rows.  Returns
    (record, stream index, first output row, end output row)."""
    spec = traffic["check"]
    streams = [(r, j, len(s["rows"])) for r in finished
               for j, s in enumerate(r["request"])]
    longest = max(range(len(streams)), key=lambda i: streams[i][2])
    others = [i for i in rng.permutation(len(streams)) if i != longest]
    sample = []
    for i in [longest] + others[:spec["streams"] - 1]:
        r, j, n = streams[i]
        lo = spec["rows"] * int(rng.integers(-(-n // spec["rows"])))
        sample.append((r, j, lo, min(n, lo + spec["rows"])))
    return sample


def decide_correct(cell, records):
    """Compares what the window committed with the plain reference.
    Returns {name: [value, limit]}; `correct` is every value <= limit."""
    cfg = cell.cfg
    h, w = cfg["video"]["height"], cfg["video"]["width"]
    finished = [r for r in records if "error" not in r]
    expected = sum(r["rows"] for r in finished)
    present = sum(cell.committed_rows(r) for r in finished)
    numbers = {"rows_missing": [abs(expected - present), 0]}
    rng = np.random.default_rng([cell.seed, 3])
    sample = check_sample(cell.traffic, finished, rng) if finished else []
    wires, outputs, id_errors = [], [], 0
    for r, j, lo, hi in sample:
        stream = r["request"][j]
        source_rows = stream["rows"][lo:hi]
        flat = cell.wire(stream["table"], source_rows)
        # output row i of the stream has to be source row rows[i]
        id_errors += sum(
            clipgen.read_barcode(wire_ref.planes(f, h, w)[0]) != row
            for f, row in zip(flat, source_rows))
        got = cell.load(r, j, range(lo, hi))
        if len(got) != hi - lo:
            numbers["rows_missing"][0] += abs(hi - lo - len(got))
            continue
        wires.extend(flat)
        outputs.extend(got)
    numbers["frame_id_errors"] = [id_errors, 0]
    numbers["nothing_compared"] = [int(not outputs), 0]
    log(f"compared {len(outputs)} committed rows of {len(sample)} streams")
    values = cell.reference.compare(cfg, wires, outputs, seed=cell.seed) \
        if outputs else {k: NEVER for k in cell.reference.LIMITS}
    for k, limit in cell.reference.LIMITS.items():
        numbers[k] = [values[k], limit]
    return numbers


def run_cell(manifest, cell_name, seed, seconds, trace, t_start,
             device, overrides=None, keep_trace=None):
    """Drives one run and returns the result line's object.  `overrides`
    shrinks a cell for a CPU test; `keep_trace` names a file to copy the
    traced run's .xplane.pb to (how tests/data was recorded)."""
    spec, cfg, traffic = load_cell(manifest, cell_name, overrides)
    peaks = load_json("peaks.json").get(device["kind"])
    if peaks is None and trace:
        raise SystemExit(f"no peaks for device kind {device['kind']!r} "
                         f"in benchmark/peaks.json")

    import jax
    workdir = tempfile.mkdtemp(prefix="scbench_")
    try:
        build_native()
        cell = Cell(cfg, traffic, seed, spec["chips"], workdir)
        t = time.time()
        for req in cell.plan["warm"]:
            rec = cell.run(req)
            if "error" in rec:
                raise RuntimeError(f"warm-up request failed: {rec['error']}")
        log(f"warm-up, {len(cell.plan['warm'])} requests: "
            f"{time.time() - t:.1f} s")
        devices = jax.local_devices()[:spec["chips"]]
        stamp = window_stamp(devices)
        stamp()  # compiles it, in set-up

        trace_dir = os.path.join(workdir, "trace")
        if trace:
            # device planes only: the host tracer's events of the TPU
            # runtime run to hundreds of MB and slow the host threefold
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0
            opts.host_tracer_level = 0
            jax.profiler.start_trace(trace_dir, profiler_options=opts)
        before = Counters(cell.sc)
        diagnostics("window opens", before)
        records = []
        stamp()
        t_open = time.time()
        for req in cell.plan["requests"]:
            records.append(cell.run(req))
            if time.time() - t_open >= seconds:
                break
        t_close = time.time()
        stamp()
        after = Counters(cell.sc)
        if trace:
            jax.profiler.stop_trace()
        diagnostics("window closed", after)
        mem = [d.memory_stats() or {} for d in devices]
        setup_s = t_open - t_start

        compiles = after.total(*REAL_COMPILE) - before.total(*REAL_COMPILE)
        if compiles:
            raise SystemExit(f"{compiles:.0f} program(s) compiled inside "
                             f"the measured window; warm-up missed a shape")

        finished = [r for r in records if "error" not in r]
        rows = sum(r["rows"] for r in finished)
        window_s = t_close - t_open
        if not trace:
            group = "end_to_end"
            lat = sorted(r["t_done"] - r["t_call"] for r in finished)
            # a failed query misses any limit: it sorts as the slowest
            lat += [NEVER] * (len(records) - len(finished))
            values = {"frames_per_s": rows / window_s, "setup_s": setup_s,
                      "job_p95_s": lat[int(np.ceil(0.95 * len(lat))) - 1]}
        else:
            group = "per_layer"
            for r in finished:
                r["intervals"] = cell.intervals(r)
            if keep_trace:
                shutil.copy(trace_reduce.find_xplane(trace_dir), keep_trace)
            reduced = read_trace(trace_dir, t_open,
                                 [iv for r in finished
                                  for iv in r["intervals"]])
            ctx = {
                "cfg": cfg, "rows": rows, "requests": finished,
                "trace": reduced, "peaks": peaks, "memory_stats": mem,
                "counter_delta": lambda s, la=None, nl=None:
                    after.total(s, la, nl) - before.total(s, la, nl),
            }
            values = {}
            for m in cell_metrics(manifest, "per_layer", cell_name):
                mdef = load_json("metrics", m["name"] + ".json")
                reader = importlib.import_module(
                    "reducers." + mdef["reducer"])
                values[m["name"]] = reader.read(ctx, **mdef.get("args", {}))

        # a reader that found nothing to read leaves its metric out
        metrics = {m["name"]: {"value": values[m["name"]], "unit": m["unit"]}
                   for m in cell_metrics(manifest, group, cell_name)
                   if values.get(m["name"]) is not None}
        dev = dict(device)
        dev["memory_peak_bytes"] = max(
            (m.get("peak_bytes_in_use", 0) for m in mem), default=0)
        breakdown = {}
        if trace and reduced is not None:
            dev["busy_s"] = reduced["busy_s"]
            dev["window_s"] = reduced["window_s"]
            breakdown = {"breakdown": {"device_ops": reduced["device_ops"],
                                       "idle_gaps": reduced["idle_gaps"]}}
        log(f"window {window_s:.2f} s, {len(records)} requests, "
            f"{rows} rows; set-up {setup_s:.1f} s; request seconds "
            + " ".join(f"{r['t_done'] - r['t_call']:.2f}" for r in records[:64]))

        # the comparison runs last: the window has closed, the peak has
        # been read, and it is not part of set-up
        t = time.time()
        numbers = decide_correct(cell, records)
        log(f"comparison with the reference: {time.time() - t:.1f} s")
        cell.sc.stop()
    finally:
        shutil.rmtree(workdir, ignore_errors=True)
    failed = len(records) - len(finished)
    return {"correct": not failed and all(v <= limit
                                          for v, limit in numbers.values()),
            "attempted": len(records), "failed": failed,
            "metrics": metrics, "device": dev, **breakdown,
            # the numbers compared, each beside its limit, come last
            "compared": {k: {"value": v, "limit": limit}
                         for k, (v, limit) in numbers.items()}}


def window_stamp(devices):
    """A tiny named program run on every chip just before the window
    opens and just after it closes, while the chip is idle: its two
    executions bound the window on the device's own clock and tie that
    clock to the host's, with no host tracer."""
    import jax

    def benchmark_window_mark(x):
        return x + 1

    fn = jax.jit(benchmark_window_mark)
    args = [jax.device_put(np.int32(0), d) for d in devices]

    def stamp():
        for a in args:
            fn(a).block_until_ready()
    return stamp


def read_trace(trace_dir, t_open, host_intervals):
    loaded = trace_reduce.load(trace_reduce.find_xplane(trace_dir))
    reduced = trace_reduce.reduce_trace(loaded)
    if reduced is None:
        return None
    reduced["idle_gaps"] = trace_reduce.label_gaps(
        reduced["gaps"], reduced["window_lo_ns"], t_open, host_intervals)
    return reduced
