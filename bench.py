"""Benchmarks: end-to-end pipeline throughput, frames/sec/chip.

BASELINE.json's north-star metric is "frames/sec/chip (pose-detect +
histogram pipelines)"; the reference repo publishes no numbers
(BASELINE.md), so the SIGGRAPH 2018 paper's ~1000 frames/sec/GPU
histogram throughput anchors vs_baseline.

Configs (BASELINE.md table):
  1 histogram      Histogram over the decoded stream
  2 shot           Histogram -> HistogramDelta temporal-diff chain
  3 pose           PoseDetect with the shipped trained weights
  4 objdet         ObjectDetect (SSD head + fixed-shape NMS)
  5 face           FaceEmbedding
  6 corpus         Histogram over a multi-video corpus in ONE bulk run
                   (BENCH_CORPUS_VIDEOS jobs through the scheduler +
                   pipeline — the corpus-shaped workload of the north
                   star, scaled to bench time)
  7 segment        InstanceSegment (detection + per-roi masks — the
                   detectron-app analog)

Prints ONE JSON line for the north-star metric (configs 1+3 averaged);
per-config detail goes to stderr and BENCH_DETAIL.json.  BENCH_CONFIGS
selects configs ("1,3" default; "all" = 1-7 incl. the corpus run);
BENCH_FRAMES / BENCH_MODEL_FRAMES / BENCH_CORPUS_VIDEOS size the decode
workloads.

Runs on the TPU JAX selects, in this one process, and nowhere else: with
no chip it exits non-zero and prints no metric line.  A digest that
fails is reported on stderr after the metric line and makes the exit
code non-zero.
"""

import json
import os
import shutil
import sys
import tempfile
import time

BASELINE_FPS = 1000.0
N_FRAMES = int(os.environ.get("BENCH_FRAMES", "600"))
# model configs run conv nets per frame; a smaller default still
# amortizes compile on TPU
N_MODEL_FRAMES = int(os.environ.get("BENCH_MODEL_FRAMES", "128"))
W, H = 640, 480
POSE_WEIGHTS = os.path.join(
    os.path.dirname(os.path.abspath(__file__)), "scanner_tpu", "models",
    "weights", "pose_blobnet_w8.npz")


N_CORPUS_VIDEOS = int(os.environ.get("BENCH_CORPUS_VIDEOS", "8"))
N_CORPUS_FRAMES = int(os.environ.get("BENCH_CORPUS_FRAMES", "120"))


def _configs():
    sel = os.environ.get("BENCH_CONFIGS", "1,3").strip().lower()
    if sel == "all":
        return [1, 2, 3, 4, 5, 6, 7]
    picked = sorted({int(x) for x in sel.split(",") if x})
    if not picked:
        print(f"bench: empty BENCH_CONFIGS={sel!r}; using default 1,3",
              file=sys.stderr)
        return [1, 3]
    return picked


# model configs: engine op + constructor args (must match pipeline())
_MODEL_CFG_OPS = {3: ("PoseDetect", {"width": 8}),
                  4: ("ObjectDetect", {"width": 8}),
                  5: ("FaceEmbedding", {"width": 8}),
                  7: ("InstanceSegment", {"width": 8})}


def _annotate_mfu(detail, errors):
    """Attach model FLOPs/frame, achieved TFLOP/s and MFU to each model
    config's record: the configs that most need the chip carry a
    utilization number, not just fps.  FLOPs come from XLA's own cost
    analysis of the kernel's jitted inference
    (models/*.infer_cost_flops); the bf16 peak from the one table keyed
    by device_kind (util/coststats.py DEVICE_PEAKS — an unknown kind
    raises)."""
    import jax
    import numpy as np

    from scanner_tpu.common import DeviceType
    from scanner_tpu.graph.ops import KernelConfig, registry
    from scanner_tpu.util.coststats import peaks_for_kind

    peak = peaks_for_kind(jax.devices()[0].device_kind)[0]
    batch = np.zeros((32, H, W, 3), np.uint8)
    cfg = KernelConfig(device=DeviceType.TPU, devices=list(jax.devices()))
    for d in detail:
        op = _MODEL_CFG_OPS.get(d.get("config"))
        if op is None:
            continue
        name, kw = op
        try:
            kern = registry.get(name).kernel_factory(cfg, **kw)
            flops = kern.infer_cost_flops(batch)
        except Exception as e:  # noqa: BLE001 — reported, exit != 0
            d["mfu_error"] = f"{type(e).__name__}: {str(e)[:120]}"
            errors.append(f"mfu config {d['config']}: {d['mfu_error']}")
            continue
        if not flops:
            continue
        per_frame = flops / len(batch)
        d["model_flops_per_frame"] = round(per_frame)
        d["achieved_tflops"] = round(per_frame * d["fps"] / 1e12, 4)
        d["mfu"] = round(per_frame * d["fps"] / peak, 6)
        d["peak_tflops"] = peak / 1e12


def main():
    import jax
    if jax.default_backend() != "tpu":
        raise SystemExit(
            f"bench: needs the TPU backend, JAX found "
            f"'{jax.default_backend()}'; a number from another platform "
            f"is not a benchmark result")
    platform = jax.devices()[0].platform
    # digest failures: reported after the metric line, exit code != 0
    errors: list = []

    def _digest(name, fn) -> dict:
        try:
            d = fn()
        except Exception as e:  # noqa: BLE001 — reported, exit != 0
            d = {"config": name, "error": f"{type(e).__name__}: {e}"}
        if d.get("error"):
            errors.append(f"{name}: {d['error']}")
        return d

    root = tempfile.mkdtemp(prefix="scbench_")
    try:
        from scanner_tpu import (CacheMode, Client, NamedStream,
                                 NamedVideoStream, PerfParams)
        import scanner_tpu.kernels   # Histogram/HistogramDelta/...
        import scanner_tpu.models    # PoseDetect/ObjectDetect/FaceEmbedding
        from scanner_tpu import video as scv

        vid = os.path.join(root, "bench.mp4")
        scv.synthesize_video(vid, num_frames=N_FRAMES, width=W, height=H,
                             fps=30, keyint=32)
        sc = Client(db_path=os.path.join(root, "db"),
                    num_load_workers=3, num_save_workers=1)
        _, _ing_failed = sc.ingest_videos([("bench", vid)])
        assert not _ing_failed, _ing_failed

        def pipeline(config: int, frames_col):
            if config == 1:
                return sc.ops.Histogram(frame=frames_col)
            if config == 2:
                hist = sc.ops.Histogram(frame=frames_col)
                return sc.ops.HistogramDelta(hist=hist)
            if config == 3:
                if not os.path.exists(POSE_WEIGHTS):
                    # still measurable perf-wise, but flag it loudly: a
                    # random-weight pose number is not the trained model
                    print(f"bench: WARNING shipped pose weights missing "
                          f"({POSE_WEIGHTS}); using random init",
                          file=sys.stderr)
                return sc.ops.PoseDetect(
                    frame=frames_col, width=8,
                    checkpoint_dir=POSE_WEIGHTS
                    if os.path.exists(POSE_WEIGHTS) else None)
            if config == 4:
                # width 8 restores the shipped trained weights by default
                return sc.ops.ObjectDetect(frame=frames_col, width=8)
            if config == 5:
                return sc.ops.FaceEmbedding(frame=frames_col, width=8)
            if config == 7:
                return sc.ops.InstanceSegment(frame=frames_col, width=8)
            raise ValueError(config)

        def run_corpus() -> dict:
            """Config 6: one bulk run over a multi-video corpus — jobs
            stream through the scheduler and the pipeline overlaps
            decode/eval/save ACROSS jobs (the corpus-shaped workload of
            the north-star metric, scaled to bench time)."""
            # one encode, N table names: the corpus shape matters to
            # the scheduler/pipeline, not the bytes
            p = os.path.join(root, "corpus.mp4")
            scv.synthesize_video(p, num_frames=N_CORPUS_FRAMES,
                                 width=W, height=H, fps=30, keyint=32)
            names = [(f"corpus_{i}", p) for i in range(N_CORPUS_VIDEOS)]
            _, _ing_failed = sc.ingest_videos(names)
            assert not _ing_failed, _ing_failed

            def run_once(suffix: str) -> float:
                streams = [NamedVideoStream(sc, n) for n, _ in names]
                frames = sc.io.Input(streams)
                hist = sc.ops.Histogram(frame=frames)
                outs = [NamedStream(sc, f"c6_{n}_{suffix}")
                        for n, _ in names]
                t0 = time.time()
                sc.run(sc.io.Output(hist, outs), PerfParams.manual(32, 96),
                       cache_mode=CacheMode.Overwrite, show_progress=False)
                return time.time() - t0

            t_warm = run_once("w")
            dt = run_once("m")
            total = N_CORPUS_VIDEOS * N_CORPUS_FRAMES
            return {"config": 6, "frames": total,
                    "videos": N_CORPUS_VIDEOS, "keyint": 32,
                    "fps": round(total / dt, 2), "platform": platform,
                    "warmup_s": round(t_warm, 2),
                    "measured_s": round(dt, 2), "reps": 1,
                    "clock": time.strftime("%Y-%m-%dT%H:%M:%S"),
                    "host_cpus": os.cpu_count()}

        def run_config(config: int) -> dict:
            if config == 6:
                return run_corpus()
            n = N_FRAMES if config in (1, 2) else min(N_FRAMES,
                                                      N_MODEL_FRAMES)

            def run_once(name: str, rows: int) -> float:
                frames = sc.io.Input([NamedVideoStream(sc, "bench")])
                ranged = sc.streams.Range(frames, [(0, rows)])
                out = NamedStream(sc, name)
                t0 = time.time()
                sc.run(sc.io.Output(pipeline(config, ranged), [out]),
                       PerfParams.manual(32, 96),
                       cache_mode=CacheMode.Overwrite, show_progress=False)
                return time.time() - t0

            # Warmup pays the jit compile and (for the decode-bound
            # configs, where a full pass is cheap) warms the page cache so
            # runs compare warm-vs-warm across rounds.  Model configs only
            # need the compile: one full work packet (32 rows) plus the
            # measured run's tail-chunk shape (n % 32), so the timed run
            # never compiles.
            warm = n if config in (1, 2) or n <= 32 else 32 + (n % 32)
            t_warm = run_once(f"warmup_{config}", warm)
            dt = run_once(f"bench_{config}", n)
            d = {"config": config, "frames": n,
                 "fps": round(n / dt, 2), "platform": platform,
                 "keyint": 32,  # round-3+: packet-aligned GOPs (was 30)
                 "warmup_frames": warm,
                 "warmup_s": round(t_warm, 2), "measured_s": round(dt, 2),
                 "reps": 1, "clock": time.strftime("%Y-%m-%dT%H:%M:%S"),
                 "host_cpus": os.cpu_count()}
            if config == 3 and not os.path.exists(POSE_WEIGHTS):
                d["weights"] = "random"
            return d

        detail = [run_config(c) for c in _configs()]
        _annotate_mfu(detail, errors)
        for d in detail:
            print(f"bench: config {d['config']}: {d['fps']} fps "
                  f"({d['frames']} frames, {d['platform']})",
                  file=sys.stderr)
        # append the live-metrics registry so perf rounds get counters
        # (recompiles, retries, bytes moved, chunk-wait seconds)
        # alongside fps — the attribution PERF.md round 3 had to
        # reconstruct from traces ships with every bench run
        from scanner_tpu.util.metrics import labeled_samples, registry
        snap = registry().snapshot()

        def per_op(series: str) -> dict:
            # sum across the remaining labels (these series carry a
            # `device` label since the multichip round: last-sample-wins
            # would report one arbitrary chip's count and mask a
            # recompile storm confined to another); the per-device
            # breakdown ships in the `multichip` digest below
            out: dict = {}
            for s in snap.get(series, {}).get("samples", []):
                k = s["labels"].get("op", "_")
                out[k] = out.get(k, 0) + s["value"]
            return out

        # shape-stability digest: with bucketed dispatch (PERF.md §3)
        # recompiles must sit at ladder size per op whatever the task
        # geometry; pad_rows is the padding waste paid for that
        detail.append({
            "config": "shape_stability",
            "recompiles": per_op("scanner_tpu_op_recompiles_total"),
            "pad_rows": per_op("scanner_tpu_op_pad_rows_total"),
            "precompile_seconds":
                per_op("scanner_tpu_op_precompile_seconds"),
        })

        def per_labels(series: str) -> dict:
            return labeled_samples(snap, series)

        # multichip digest: did the bench's bulks actually spread across
        # this host's chips (evaluator affinity, PERF.md §3)?  tasks and
        # busy seconds per assigned device, plus per-(op, device)
        # executable counts — a chip at 0 while siblings climb is the
        # regression this series exists to catch
        detail.append({
            "config": "multichip",
            "n_devices": len(jax.local_devices()),
            "affinity": os.environ.get(
                "SCANNER_TPU_DEVICE_AFFINITY", "1") not in ("0", "false"),
            "device_tasks": per_labels("scanner_tpu_device_tasks_total"),
            "evaluate_open_seconds":
                per_labels("scanner_tpu_evaluate_open_seconds_total"),
            "recompiles_by_device":
                per_labels("scanner_tpu_op_recompiles_total"),
        })
        # memory digest (util/memstats.py): peak HBM per device (backend
        # view), the allocation ledger's peaks per (device, kind) —
        # staged columns vs warm-up args vs sink batches — and the
        # padding waste bucketed dispatch paid, in approximate bytes
        # (pad rows x decoded-frame bytes; exact per-op row widths are
        # not knowable from counters alone)
        pad_rows_total = sum(
            s["value"] for s in snap.get(
                "scanner_tpu_op_pad_rows_total", {}).get("samples", []))
        from scanner_tpu.util import memstats as _memstats
        detail.append({
            "config": "memory",
            "device_hbm": _memstats.device_memory_stats(),
            "device_hbm_peak_bytes":
                per_labels("scanner_tpu_device_hbm_peak_bytes"),
            "ledger_peak_bytes":
                per_labels("scanner_tpu_ledger_peak_bytes"),
            "ledger_live_bytes":
                per_labels("scanner_tpu_ledger_live_bytes"),
            "staged_bytes_total": sum(
                s["value"] for s in snap.get(
                    "scanner_tpu_h2d_bytes_total", {}).get("samples", [])),
            "pad_rows_total": pad_rows_total,
            "pad_waste_bytes_approx": int(pad_rows_total * W * H * 3),
            "oom_events": sum(
                s["value"] for s in snap.get(
                    "scanner_tpu_device_oom_events_total",
                    {}).get("samples", [])),
        })

        # quantile estimation shared with the health/SLO engine and
        # tools (scanner_tpu.util.metrics.histogram_quantile)
        from scanner_tpu.util.metrics import snapshot_histogram_quantiles

        def hist_quantiles(series: str, qs=(0.5, 0.9, 0.99)) -> dict:
            return snapshot_histogram_quantiles(snap, series, qs)

        # end-to-end per-task latency digest (enqueue -> sink-committed):
        # the serving-mode p50/p99 seed (ROADMAP item 2) banked per
        # round so the latency trajectory ships with the fps one.
        # Computed once; the baseline_metrics entry below reuses it so
        # the two banked views can never disagree.
        _tlq = hist_quantiles("scanner_tpu_task_latency_seconds")
        detail.append({"config": "task_latency", **_tlq})
        # compute-efficiency digest (util/coststats.py): the roofline
        # table per (op, device, bucket) — achieved FLOP/s / bytes/s
        # and the compute-vs-memory-bound verdict — plus the compile
        # ledger summary with the persistent-cache hit rate.  The
        # baseline instrument the ROADMAP perf items (pjit mesh, Pallas
        # scan kernels, frame cache) are judged against.
        from scanner_tpu.util import coststats as _coststats
        _eff_ops = _coststats.op_efficiency()
        _csum = _coststats.ledger_summary()
        detail.append({
            "config": "op_efficiency",
            "ops": _eff_ops,
            "compile": _csum,
        })
        # frame-cache digest (engine/framecache.py): the cross-task
        # reuse A/B the acceptance gate reads — cache-on cold+warm
        # passes over the same clip vs a SCANNER_TPU_FRAME_CACHE=0 run,
        # with decode seconds and h2d bytes saved measured from the
        # shared counters (the cache bills the same h2d meter direct
        # staging does, so the comparison is like for like)
        from scanner_tpu.engine import framecache as _framecache

        def _fc_digest() -> dict:
            if not _framecache.enabled():
                return {"config": "frame_cache", "enabled": False}
            n_fc = min(N_FRAMES, 288)

            def tot(name: str) -> float:
                s = registry().snapshot().get(name, {})
                return sum(x["value"] for x in s.get("samples", []))

            def measured(name: str) -> dict:
                d0 = tot("scanner_tpu_decode_seconds_total")
                b0 = tot("scanner_tpu_h2d_bytes_total")
                frames = sc.io.Input([NamedVideoStream(sc, "bench")])
                ranged = sc.streams.Range(frames, [(0, n_fc)])
                out = NamedStream(sc, name)
                t0 = time.time()
                sc.run(sc.io.Output(sc.ops.Histogram(frame=ranged),
                                    [out]), PerfParams.manual(32, 96),
                       cache_mode=CacheMode.Overwrite,
                       show_progress=False)
                return {
                    "wall_s": round(time.time() - t0, 3),
                    "decode_s": round(
                        tot("scanner_tpu_decode_seconds_total") - d0, 4),
                    "h2d_bytes": tot("scanner_tpu_h2d_bytes_total") - b0,
                }

            try:
                _framecache.cache().clear()
                h0 = tot("scanner_tpu_framecache_hits_total")
                m0 = tot("scanner_tpu_framecache_misses_total")
                on_cold = measured("fc_on_cold")
                h1 = tot("scanner_tpu_framecache_hits_total")
                m1 = tot("scanner_tpu_framecache_misses_total")
                on_warm = measured("fc_on_warm")
                h2 = tot("scanner_tpu_framecache_hits_total")
                m2 = tot("scanner_tpu_framecache_misses_total")
                hits, misses = h2 - h0, m2 - m0
                wh, wm = h2 - h1, m2 - m1
                _framecache.set_enabled(False)
                off = measured("fc_off")
                return {
                    "config": "frame_cache", "enabled": True,
                    "frames": n_fc,
                    # combined A/B rate (cold fill + warm reuse) AND the
                    # warm-pass rate — the hot-clip/second-pipeline
                    # scenario the cache exists for, and the number the
                    # acceptance gate + baseline direction track
                    "hit_rate": round(hits / (hits + misses), 4)
                    if hits + misses else None,
                    "warm_hit_rate": round(wh / (wh + wm), 4)
                    if wh + wm else None,
                    "hits": hits, "misses": misses,
                    "on_cold": on_cold, "on_warm": on_warm, "off": off,
                    "decode_seconds_saved": round(
                        off["decode_s"] - on_warm["decode_s"], 4),
                    "h2d_bytes_saved":
                        off["h2d_bytes"] - on_warm["h2d_bytes"],
                }
            finally:
                _framecache.set_enabled(True)

        _fc_d = _fc_digest()
        detail.append(_fc_d)

        # remediation digest (engine/controller.py): a bounded live
        # preemption drill — tiny in-process cluster, one of two
        # workers preempted mid-bulk (the worker.preempt chaos site) —
        # banking the recovery time (preemption notice -> bulk
        # complete, i.e. how fast the cluster re-absorbs reclaimed
        # capacity's work) plus the controller's decision counters, so
        # tools/bench_history.py gates the close-the-loop trajectory
        # like any other metric
        def _remediation_digest() -> dict:
            import struct as _struct
            import threading as _threading

            from scanner_tpu import Kernel, register_op
            from scanner_tpu.engine import controller as _ctrl
            from scanner_tpu.engine.service import Master, Worker
            from scanner_tpu.util import faults as _faults

            if not _ctrl.enabled():
                return {"config": "remediation", "enabled": False}

            def _pk(v: int) -> bytes:
                return _struct.pack("<q", v)

            @register_op(name="BenchRemSleep")
            class BenchRemSleep(Kernel):
                # slow enough that the bulk (24 tasks across 2
                # workers) outlives the 2nd-heartbeat preemption at
                # ~2 s — the drill must reclaim capacity MID-bulk
                def execute(self, x: bytes) -> bytes:
                    time.sleep(0.2)
                    return _pk(2 * _struct.unpack("<q", x)[0])

            def _tot(name: str) -> float:
                s = registry().snapshot().get(name, {})
                return sum(x["value"] for x in s.get("samples", []))

            def _by_labels(name: str) -> dict:
                return labeled_samples(registry().snapshot(), name)

            rdb = os.path.join(root, "rem_db")
            n_rows = 48
            seed2 = Client(db_path=rdb)
            seed2.new_table("rem_src", ["output"],
                            [[_pk(100 + i)] for i in range(n_rows)])
            master = Master(db_path=rdb, no_workers_timeout=30.0)
            addr = f"localhost:{master.port}"
            workers = [Worker(addr, db_path=rdb) for _ in range(2)]
            rc = Client(db_path=rdb, master=addr)
            strikes0 = _tot("scanner_tpu_blacklist_strikes_total")
            trans0 = {k: v for k, v in _by_labels(
                "scanner_tpu_alerts_transitions_total").items()}
            victim = workers[0]
            preempt_at = [None]

            def _watch() -> None:
                while preempt_at[0] is None:
                    if victim.preempting():
                        preempt_at[0] = time.time()
                        return
                    time.sleep(0.01)

            try:
                _faults.install(
                    f"worker.preempt:raise:"
                    f"match={victim.worker_id}:n=2:times=1")
                w_t = _threading.Thread(target=_watch, daemon=True)
                w_t.start()
                col = rc.io.Input([NamedStream(rc, "rem_src")])
                col = rc.ops.BenchRemSleep(x=col)
                out = NamedStream(rc, "rem_out")
                rc.run(rc.io.Output(col, [out]),
                       PerfParams.manual(2, 2),
                       cache_mode=CacheMode.Overwrite,
                       show_progress=False)
                done_at = time.time()
                rows_ok = len(list(out.load())) == n_rows
                recovery = round(done_at - preempt_at[0], 3) \
                    if preempt_at[0] is not None \
                    and preempt_at[0] < done_at else None
                trans1 = _by_labels(
                    "scanner_tpu_alerts_transitions_total")
                return {
                    "config": "remediation", "enabled": True,
                    "rows_ok": rows_ok,
                    "preemption_recovery_s": recovery,
                    "preemptions": _tot(
                        "scanner_tpu_worker_preemptions_total"),
                    "preempt_notices": _tot(
                        "scanner_tpu_worker_preempt_notices_total"),
                    "strike_delta": _tot(
                        "scanner_tpu_blacklist_strikes_total")
                    - strikes0,
                    "alert_transitions": {
                        k: v - trans0.get(k, 0.0)
                        for k, v in trans1.items()
                        if v - trans0.get(k, 0.0)},
                    "remediations": _by_labels(
                        "scanner_tpu_remediations_total"),
                }
            finally:
                _faults.clear()
                rc.stop()
                for w in workers:
                    w.stop()
                master.stop()

        _rem_d = _digest("remediation", _remediation_digest)
        detail.append(_rem_d)

        # failover digest (engine/journal.py): a bounded live master-
        # failover drill — in-process 2-worker cluster, the master
        # stopped abruptly mid-bulk (no checkpoint clear, journal-only
        # durability: checkpoint_frequency=0) and a successor started
        # on the same port — banking the recovery time (kill -> bulk
        # complete) and how many acknowledged completions the
        # successor failed to restore (the journal's whole point: 0),
        # so tools/bench_history.py gates the durable-control-plane
        # trajectory like any other metric
        def _failover_digest() -> dict:
            import socket as _socket
            import struct as _struct
            import threading as _threading

            from scanner_tpu import Kernel, register_op
            from scanner_tpu.engine.service import Master, Worker

            def _pk(v: int) -> bytes:
                return _struct.pack("<q", v)

            def _tot(name: str) -> float:
                s = registry().snapshot().get(name, {})
                return sum(x["value"] for x in s.get("samples", []))

            @register_op(name="BenchFoSleep")
            class BenchFoSleep(Kernel):
                # slow enough that the bulk (24 tasks across 2
                # workers) outlives the mid-bulk master kill
                def execute(self, x: bytes) -> bytes:
                    time.sleep(0.15)
                    return _pk(2 * _struct.unpack("<q", x)[0])

            fdb = os.path.join(root, "fo_db")
            n_rows = 48
            seedf = Client(db_path=fdb)
            seedf.new_table("fo_src", ["output"],
                            [[_pk(100 + i)] for i in range(n_rows)])
            with _socket.socket() as s:
                s.bind(("localhost", 0))
                port = s.getsockname()[1]
            m1 = Master(db_path=fdb, port=port, no_workers_timeout=60.0)
            addr = f"localhost:{port}"
            workers = [Worker(addr, db_path=fdb) for _ in range(2)]
            fc = Client(db_path=fdb, master=addr)
            result: dict = {}
            m2 = None

            def _job() -> None:
                try:
                    col = fc.io.Input([NamedStream(fc, "fo_src")])
                    col = fc.ops.BenchFoSleep(x=col)
                    out = NamedStream(fc, "fo_out")
                    fc.run(fc.io.Output(col, [out]),
                           PerfParams.manual(2, 2,
                                             checkpoint_frequency=0),
                           cache_mode=CacheMode.Overwrite,
                           show_progress=False)
                    result["rows"] = len(list(out.load()))
                except Exception as e:  # noqa: BLE001
                    result["error"] = f"{type(e).__name__}: {e}"

            try:
                jt = _threading.Thread(target=_job, daemon=True)
                jt.start()
                deadline = time.time() + 60
                while time.time() < deadline:
                    with m1._lock:
                        b = m1._bulk
                        if b is not None and len(b.done) >= 4:
                            break
                    time.sleep(0.02)
                m1.stop()  # abrupt: bulk still active, nothing cleared
                with m1._lock:
                    done_at_kill = len(m1._bulk.done) \
                        if m1._bulk else 0
                kill_at = time.time()
                # successor on the SAME port (workers redial it); the
                # just-freed port can linger briefly
                for _ in range(20):
                    try:
                        m2 = Master(db_path=fdb, port=port,
                                    no_workers_timeout=60.0)
                        break
                    except Exception:  # noqa: BLE001 — port lingering
                        time.sleep(0.25)
                restored = 0
                if m2 is not None:
                    with m2._lock:
                        restored = len(m2._bulk.done) \
                            if m2._bulk else 0
                jt.join(timeout=120)
                done_at = time.time()
                recovery = round(done_at - kill_at, 3) \
                    if result.get("rows") == n_rows else None
                return {
                    "config": "failover",
                    "rows_ok": result.get("rows") == n_rows,
                    "error": result.get("error"),
                    "done_at_kill": done_at_kill,
                    "done_restored": restored,
                    "tasks_lost_on_recovery":
                        max(0, done_at_kill - restored),
                    "failover_recovery_s": recovery,
                    "journal_appends": _tot(
                        "scanner_tpu_journal_appends_total"),
                    "journal_replayed": _tot(
                        "scanner_tpu_journal_replayed_records_total"),
                }
            finally:
                fc.stop()
                for w in workers:
                    w.stop()
                if m2 is not None:
                    m2.stop()

        _fo_d = _digest("failover", _failover_digest)
        detail.append(_fo_d)

        # whole-pipeline fusion digest (graph/fusion.py, PERF.md §3):
        # the golden Resize->Blur->Histogram->HistDiff pipeline run
        # staged (SCANNER_TPU_FUSION semantics, fusion.set_enabled off)
        # then fused over the same clip.  Banked: the per-mode wall
        # seconds of the run, the executables each mode minted, the
        # intermediate HBM bytes the fused program never materialized,
        # and the direction-gated fused_chain_speedup = staged wall
        # seconds / fused wall seconds of the warm passes (host seconds
        # around the ops' asynchronous calls are no longer counted)
        def _fusion_digest() -> dict:
            from scanner_tpu.graph import fusion as _fusion

            members = ("Resize", "Blur", "Histogram", "HistDiff")
            # HistDiff (windowed, non-head) stays staged; the planner
            # forms the 3-member chain
            cid = "+".join(members[:3])

            def _by_op(name: str) -> dict:
                out: dict = {}
                for s in registry().snapshot().get(
                        name, {}).get("samples", []):
                    k = s["labels"].get("op", "_")
                    out[k] = out.get(k, 0.0) + s["value"]
                return out

            fdb = os.path.join(root, "fusion_db")
            n_rows = 96
            fvid = os.path.join(root, "fusion.mp4")
            scv.synthesize_video(fvid, num_frames=n_rows, width=W,
                                 height=H, fps=24, keyint=24)
            fc5 = Client(db_path=fdb)
            fc5.ingest_videos([("fz_vid", fvid)])
            keys = (cid,) + members

            def _run_mode(mode: str, on: bool) -> dict:
                prev = _fusion.enabled()
                _fusion.set_enabled(on)
                try:
                    r0 = _by_op("scanner_tpu_op_recompiles_total")
                    col = fc5.io.Input(
                        [NamedVideoStream(fc5, "fz_vid")])
                    col = fc5.ops.Resize(frame=col, width=[W // 2],
                                         height=[H // 2])
                    col = fc5.ops.Blur(frame=col, kernel_size=3,
                                       sigma=1.1)
                    col = fc5.ops.Histogram(frame=col)
                    col = fc5.ops.HistDiff(frame=col)
                    out = NamedStream(fc5, f"fz_{mode}")
                    w0 = time.time()
                    fc5.run(fc5.io.Output(col, [out]),
                            PerfParams.manual(8, 16),
                            cache_mode=CacheMode.Overwrite,
                            show_progress=False)
                    wall = time.time() - w0
                    rows = len(list(out.load()))
                    r1 = _by_op("scanner_tpu_op_recompiles_total")
                    return {
                        "mode": mode,
                        "rows_ok": rows == n_rows,
                        "wall_s": round(wall, 3),
                        "executables_minted": int(
                            sum(r1.get(k, 0) - r0.get(k, 0)
                                for k in keys)),
                    }
                finally:
                    _fusion.set_enabled(prev)

            try:
                # cold pass per mode mints the executables; the banked
                # speedup comes from a second, warm pass so one-off
                # trace/compile time doesn't swamp the steady-state A/B
                staged = _run_mode("staged", on=False)
                fused = _run_mode("fused", on=True)
                staged_w = _run_mode("staged_warm", on=False)
                fused_w = _run_mode("fused_warm", on=True)
                speedup = None
                if staged_w["wall_s"] and fused_w["wall_s"]:
                    speedup = round(staged_w["wall_s"]
                                    / fused_w["wall_s"], 3)
                snap_f = registry().snapshot()
                saved = sum(
                    s["value"] for s in snap_f.get(
                        "scanner_tpu_fusion_intermediate_bytes_saved_"
                        "total", {}).get("samples", [])
                    if s["labels"].get("chain") == cid)
                chains = {
                    s["labels"]["chain"]: s["value"]
                    for s in snap_f.get(
                        "scanner_tpu_fusion_chains_planned",
                        {}).get("samples", [])}
                return {
                    "config": "fusion",
                    "rows_ok": (staged["rows_ok"] and fused["rows_ok"]
                                and staged_w["rows_ok"]
                                and fused_w["rows_ok"]),
                    "error": None,
                    "chain": cid,
                    "chains_planned": chains,
                    "staged": staged,
                    "fused": fused,
                    "staged_warm": staged_w,
                    "fused_warm": fused_w,
                    "fused_chain_speedup": speedup,
                    "executables_avoided":
                        staged["executables_minted"]
                        - fused["executables_minted"],
                    "intermediate_bytes_saved": saved,
                }
            finally:
                fc5.stop()

        _fz_d = _digest("fusion", _fusion_digest)
        detail.append(_fz_d)

        # control-plane digest (engine/shardmap.py): a bounded live
        # sharded-master drill — two in-process shard masters, one
        # multiplexing worker.  Admission is probed per shard (NewJob
        # wall time; p99 = worst probe on the worst shard), then a
        # bulk owned by the NON-dialed shard is killed mid-flight
        # (checkpoint_frequency=0: journal-only durability) and a
        # successor started on the same port — banking shard-failover
        # recovery seconds and the FinishedWork coalescing yield so
        # tools/bench_history.py gates the sharded control plane like
        # any other metric
        def _control_plane_digest() -> dict:
            import socket as _socket
            import struct as _struct

            import cloudpickle as _cp

            from scanner_tpu import Kernel, register_op
            from scanner_tpu.engine import shardmap as _shmap
            from scanner_tpu.engine.service import Master, Worker

            def _pk(v: int) -> bytes:
                return _struct.pack("<q", v)

            def _tot(name: str, method: str = None) -> float:
                s = registry().snapshot().get(name, {})
                return sum(
                    x["value"] for x in s.get("samples", [])
                    if method is None
                    or x.get("labels", {}).get("method") == method)

            @register_op(name="BenchCpFast")
            class BenchCpFast(Kernel):
                def execute(self, x: bytes) -> bytes:
                    return _pk(2 * _struct.unpack("<q", x)[0])

            @register_op(name="BenchCpSlow")
            class BenchCpSlow(Kernel):
                # slow enough that the bulk outlives the mid-bulk
                # shard kill
                def execute(self, x: bytes) -> bytes:
                    time.sleep(0.15)
                    return _pk(3 * _struct.unpack("<q", x)[0])

            cdb = os.path.join(root, "cp_db")
            n_rows = 48
            os.environ["SCANNER_TPU_CONTROL_SHARDS"] = "2"
            _shmap.set_num_shards(2)
            seedc = Client(db_path=cdb)
            seedc.new_table("cp_src", ["output"],
                            [[_pk(100 + i)] for i in range(n_rows)])
            # spec blobs come from FRESH clients so each admission
            # sees the master-created tables of the previous one
            # (client-side table-id allocation is single-writer);
            # each client stays alive until its bulk drains
            spec_clients: list = []

            def _spec(op: str, out_name: str, **perf_kw) -> bytes:
                c = Client(db_path=cdb)
                spec_clients.append(c)
                col = c.io.Input([NamedStream(c, "cp_src")])
                col = getattr(c.ops, op)(x=col)
                node = c.io.Output(col, [NamedStream(c, out_name)])
                return _cp.dumps({
                    "outputs": [node],
                    "perf": PerfParams.manual(2, 2, **perf_kw),
                    "cache_mode": CacheMode.Overwrite.value})

            ports = []
            for _ in range(2):
                with _socket.socket() as s:
                    s.bind(("localhost", 0))
                    ports.append(s.getsockname()[1])
            masters = [Master(db_path=cdb, port=ports[k], shard_id=k,
                              num_shards=2, no_workers_timeout=60.0)
                       for k in range(2)]
            worker = Worker(f"localhost:{ports[0]}", db_path=cdb)
            successor = None
            coal_fw0 = _tot("scanner_tpu_rpc_coalesced_total",
                            "FinishedWork")

            def _drain(m, bulk_id: int, timeout_s: float) -> dict:
                end = time.time() + timeout_s
                st: dict = {}
                while time.time() < end:
                    st = m._rpc_job_status({"bulk_id": bulk_id})
                    if st.get("finished"):
                        return st
                    time.sleep(0.1)
                return st

            try:
                deadline = time.time() + 30
                while time.time() < deadline \
                        and len(worker._links) < 2:
                    time.sleep(0.05)
                if len(worker._links) < 2:
                    return {"config": "control_plane",
                            "error": "worker never linked both shards"}
                # admission probes, sequential per shard (the serial
                # admission path is what the p99 judges)
                tasks_done = 0.0
                admit: list = []
                for sid in range(2):
                    for i in range(3):
                        blob = _spec("BenchCpFast",
                                     f"cp_probe_{sid}_{i}")
                        t0 = time.time()
                        r = masters[sid]._rpc_new_job(
                            {"spec": blob,
                             "token": f"cp-probe-{sid}-{i}"})
                        admit.append(time.time() - t0)
                        if "bulk_id" not in r:
                            return {"config": "control_plane",
                                    "error": f"admission NACK: {r}"}
                        st = _drain(masters[sid], r["bulk_id"], 60)
                        if not st.get("finished"):
                            return {
                                "config": "control_plane",
                                "error": f"probe bulk stuck on shard "
                                         f"{sid}: {st.get('error')}"}
                        tasks_done += st.get("tasks_done") or 0
                # shard failover: the job lands on shard 1 — the
                # NON-dialed shard, so recovery also proves the
                # worker's multiplexed link redials the successor
                blob = _spec("BenchCpSlow", "cp_fo_out",
                             checkpoint_frequency=0)
                r = masters[1]._rpc_new_job(
                    {"spec": blob, "token": "cp-fo"})
                if "bulk_id" not in r:
                    return {"config": "control_plane",
                            "error": f"failover admission NACK: {r}"}
                bulk_id = r["bulk_id"]
                end = time.time() + 60
                done_at_kill = 0
                while time.time() < end:
                    st = masters[1]._rpc_job_status(
                        {"bulk_id": bulk_id})
                    if (st.get("tasks_done") or 0) >= 4:
                        done_at_kill = st["tasks_done"]
                        break
                    time.sleep(0.05)
                masters[1].stop()  # abrupt: bulk active, no cleanup
                kill_at = time.time()
                for _ in range(20):
                    try:
                        successor = Master(
                            db_path=cdb, port=ports[1], shard_id=1,
                            num_shards=2, no_workers_timeout=60.0)
                        break
                    except Exception:  # noqa: BLE001 — port lingering
                        time.sleep(0.25)
                if successor is None:
                    return {"config": "control_plane",
                            "error": "successor never bound the port"}
                st = _drain(successor, bulk_id, 120)
                recovery = round(time.time() - kill_at, 3) \
                    if st.get("finished") else None
                tasks_done += st.get("tasks_done") or 0
                rows = None
                vc = Client(db_path=cdb)
                try:
                    rows = len(list(
                        NamedStream(vc, "cp_fo_out").load()))
                finally:
                    vc.stop()
                coal_fw = _tot("scanner_tpu_rpc_coalesced_total",
                               "FinishedWork") - coal_fw0
                return {
                    "config": "control_plane",
                    "rows_ok": rows == n_rows,
                    "done_at_kill": done_at_kill,
                    "per_shard_admission_p99_s": round(max(admit), 4),
                    "shard_failover_recovery_s": recovery,
                    "shard_failovers": _tot(
                        "scanner_tpu_shard_failovers_total"),
                    "shard_journal_reexec": _tot(
                        "scanner_tpu_shard_journal_reexec_total"),
                    "finished_coalesced": coal_fw,
                    "finished_coalescing_ratio": round(
                        coal_fw / tasks_done, 4)
                        if tasks_done else None,
                }
            finally:
                for obj in ([worker] + masters
                            + ([successor] if successor else [])
                            + spec_clients + [seedc]):
                    try:
                        obj.stop()
                    except Exception:  # noqa: BLE001 — teardown of an
                        pass           # already-stopped shard
                os.environ.pop("SCANNER_TPU_CONTROL_SHARDS", None)
                _shmap.set_num_shards(1)

        _cp_d = _digest("control_plane", _control_plane_digest)
        detail.append(_cp_d)
        # stable per-direction baseline keys (ROADMAP "bank per-item
        # baselines for the new directions"): one flat entry with a
        # declared better= direction per metric, so
        # tools/bench_history.py can gate the serving (task-latency
        # p99), cache (compile-cache hit rate) and scan/kernel (per-op
        # efficiency) directions from the first round that banks a
        # baseline (bench_history.py --write-baselines).  The mean is
        # WEIGHTED by measured seconds: an unweighted mean over
        # whichever (op, device, bucket) rows a round happened to hit
        # would swing on a rarely-run tail bucket's noisy sample and
        # trip the gate with no real change.
        _eff_w = sum(o["seconds"] for o in _eff_ops
                     if o.get("efficiency") is not None)
        _eff_mean = (round(sum(o["efficiency"] * o["seconds"]
                               for o in _eff_ops
                               if o.get("efficiency") is not None)
                           / _eff_w, 6) if _eff_w else None)
        detail.append({
            "config": "baseline_metrics",
            "metrics": {
                "task_latency_p99_s": {
                    "value": _tlq.get("p99_s"), "better": "lower"},
                "op_efficiency_mean": {
                    "value": _eff_mean, "better": "higher"},
                "compile_cache_hit_rate": {
                    "value": _csum.get("cache_hit_rate"),
                    "better": "higher"},
                "frame_cache_hit_rate": {
                    "value": _fc_d.get("warm_hit_rate"),
                    "better": "higher"},
                "frame_cache_decode_seconds_saved": {
                    "value": _fc_d.get("decode_seconds_saved"),
                    "better": "higher"},
                "frame_cache_h2d_bytes_saved": {
                    "value": _fc_d.get("h2d_bytes_saved"),
                    "better": "higher"},
                "preemption_recovery_s": {
                    "value": _rem_d.get("preemption_recovery_s"),
                    "better": "lower"},
                "failover_recovery_s": {
                    "value": _fo_d.get("failover_recovery_s"),
                    "better": "lower"},
                "tasks_lost_on_recovery": {
                    "value": _fo_d.get("tasks_lost_on_recovery"),
                    "better": "lower"},
                "fused_chain_speedup": {
                    "value": _fz_d.get("fused_chain_speedup"),
                    "better": "higher"},
                "shard_failover_recovery_s": {
                    "value": _cp_d.get("shard_failover_recovery_s"),
                    "better": "lower"},
                "per_shard_admission_p99_s": {
                    "value": _cp_d.get("per_shard_admission_p99_s"),
                    "better": "lower"},
            },
        })
        # health digest (util/health.py): alert transitions fired during
        # this bench run plus the latency-quantile snapshot the SLO
        # rules judge — tools/bench_history.py reads this trajectory so
        # a round that alerted is visible next to its fps
        from scanner_tpu.util import health as _health
        _alert_transitions: dict = {}
        for s in snap.get("scanner_tpu_alerts_transitions_total",
                          {}).get("samples", []):
            lbl = s.get("labels", {})
            key = f"{lbl.get('rule', '?')}:{lbl.get('state', '?')}"
            _alert_transitions[key] = _alert_transitions.get(key, 0.0) \
                + s.get("value", 0.0)
        _hstat = _health.status_dict()
        detail.append({
            "config": "health",
            "status": _hstat.get("status"),
            "reasons": _hstat.get("reasons"),
            "firing": _hstat.get("firing"),
            "alert_transitions": _alert_transitions,
            "task_latency":
                hist_quantiles("scanner_tpu_task_latency_seconds"),
            "rpc_latency":
                hist_quantiles("scanner_tpu_rpc_latency_seconds"),
        })
        detail.append({"config": "metrics_registry", "snapshot": snap})
        # static-analysis digest: finding counts per code ride with every
        # perf round, so analyzer drift (new findings, baseline growth)
        # is visible in the same trajectory as fps regressions
        def _static_analysis_digest() -> dict:
            from scanner_tpu.analysis.static import (
                analyze, load_baseline, split_findings)
            _root = os.path.dirname(os.path.abspath(__file__))
            _sc_t0 = time.perf_counter()
            _proj, _found = analyze(
                [os.path.join(_root, "scanner_tpu")], root=_root)
            _sc_s = round(time.perf_counter() - _sc_t0, 3)
            _res = split_findings(_proj, _found, load_baseline(
                os.path.join(_root, "tools",
                             "scanner_check_baseline.json")))
            _counts: dict = {}
            for _f in _found:
                _counts[_f.code] = _counts.get(_f.code, 0) + 1
            return {
                "config": "static_analysis",
                "findings_by_code": _counts,
                "unsuppressed": len(_res.unsuppressed),
                "baselined": len(_res.baselined),
                "inline_suppressed": len(_res.inline_suppressed),
                "files_analyzed": len(_proj.modules),
                "scanner_check_seconds": _sc_s,
            }

        _sa_d = _digest("static_analysis", _static_analysis_digest)
        detail.append(_sa_d)
        if "scanner_check_seconds" in _sa_d:
            # direction-gated wall clock for the full four-family run
            # over ONE shared Project — the analyzer's perf budget is
            # banked and regression-gated like any serving metric
            # (tools/bench_history.py --write-baselines)
            for _d in detail:
                if _d.get("config") == "baseline_metrics":
                    _d["metrics"]["scanner_check_seconds"] = {
                        "value": _sa_d["scanner_check_seconds"],
                        "better": "lower"}
        with open(os.path.join(os.path.dirname(os.path.abspath(__file__)),
                               "BENCH_DETAIL.json"), "w") as f:
            json.dump(detail, f, indent=1)

        by_cfg = {d["config"]: d["fps"] for d in detail if "fps" in d}
        if 1 in by_cfg and 3 in by_cfg:
            value = round((by_cfg[1] + by_cfg[3]) / 2.0, 2)
            metric = "histogram+pose_pipeline_throughput"
        else:
            value = detail[0]["fps"]
            metric = f"config{detail[0]['config']}_pipeline_throughput"
        print(json.dumps({
            "metric": metric,
            "value": value,
            "unit": "frames/sec/chip",
            "vs_baseline": round(value / BASELINE_FPS, 4),
        }))
    finally:
        shutil.rmtree(root, ignore_errors=True)
    if errors:
        for e in errors:
            print(f"bench: FAILED digest {e}", file=sys.stderr)
        raise SystemExit(1)


if __name__ == "__main__":
    main()
