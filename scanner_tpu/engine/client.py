"""Client: the user entry point.

Capability parity: reference scannerpy/client.py (Client:58, run:1282,
ingest_videos:965, new_table:418, table:500, summarize:548) — here the
single-node path runs in-process; engine/service.py provides the
master/worker cluster path behind the same API.
"""

from __future__ import annotations

import os
from typing import Any, Dict, List, Optional, Sequence, Union

from ..common import CacheMode, DeviceType, PerfParams, ScannerException
from ..graph import analysis as A
from ..graph import ops as O
from ..graph.streams_dsl import IOGenerator, StreamsGenerator, TaskPartitioner
from ..storage import Database, make_storage
from ..storage import metadata as md
from ..storage.streams import NamedStream, NamedVideoStream
from ..util.profiler import Profile, Profiler
from .evaluate import EvaluatorPool
from .executor import LocalExecutor


class Table:
    """Read handle on a stored table (reference table.py:11)."""

    def __init__(self, db: Database, name: str):
        self._db = db
        self._name = name

    def id(self) -> int:
        return self._db.table_descriptor(self._name).id

    def name(self) -> str:
        return self._name

    def num_rows(self) -> int:
        return self._db.table_descriptor(self._name).num_rows

    def column_names(self) -> List[str]:
        return self._db.table_descriptor(self._name).column_names()

    def column(self, name: str):
        desc = self._db.table_descriptor(self._name)
        if desc.column_type(name) == md.ColumnType.VIDEO:
            s = NamedVideoStream(self._db, self._name)
        else:
            s = NamedStream(self._db, self._name)
            if name != "output":
                # direct column access bypasses the default-column logic
                return _ColumnReader(self._db, self._name, name)
        return s

    def committed(self) -> bool:
        return self._db.table_is_committed(self._name)


class _ColumnReader:
    def __init__(self, db: Database, table: str, column: str):
        self._stream = NamedStream(db, table)
        self._column = column

    def load(self, rows: Optional[Sequence[int]] = None):
        yield from self._stream.load(rows=rows, column=self._column)


class Client:
    """Create one per database.

    sc = Client(db_path="/data/db")
    frames = sc.io.Input([NamedVideoStream(sc, "movie", path="m.mp4")])
    hist = sc.ops.Histogram(frame=frames)
    sc.run(sc.io.Output(hist, [NamedStream(sc, "hists")]), PerfParams.estimate())
    """

    def __init__(self, db_path: Optional[str] = None,
                 storage_type: Optional[str] = None,
                 master: Optional[str] = None,
                 workers: Optional[List[str]] = None,
                 # None = derived at each run from the cores this
                 # process may use, the run's evaluator instances, its
                 # tasks and, where they load whole, its queue depth
                 # (engine/evaluate.py default_load_workers).  An
                 # explicit value wins.
                 num_load_workers: Optional[int] = None,
                 num_save_workers: int = 2,
                 # None = resolve at job launch: one device-affine
                 # instance per local chip on multi-device hosts
                 # (engine/evaluate.py default_pipeline_instances).  An
                 # explicit value — including 1 — always wins.
                 pipeline_instances: Optional[int] = None,
                 decoder_threads: int = 1,
                 config_path: Optional[str] = None,
                 storage_options: Optional[Dict[str, Any]] = None,
                 metrics_port: Optional[int] = None,
                 **kw):
        if config_path is not None:
            from ..config import Config
            cfg = Config(config_path)
            db_path = db_path or cfg.db_path
            # [trace] enabled: the deployment-wide tracing default; the
            # SCANNER_TPU_TRACING env var (read at import) is the
            # per-process override and wins when set.  Applied in both
            # directions so a later Client with an enabling config
            # isn't stuck with an earlier one's disable.
            if not os.environ.get("SCANNER_TPU_TRACING"):
                from ..util import tracing
                tracing.set_enabled(cfg.tracing_enabled)
            # [trace] clocksync_enabled / rebase_clocks: cross-host
            # clock-offset estimation + trace-assembly rebase defaults;
            # SCANNER_TPU_CLOCKSYNC (read at import) wins when set
            from ..util import clocksync as _clk_cfg
            if not os.environ.get("SCANNER_TPU_CLOCKSYNC"):
                _clk_cfg.set_enabled(cfg.clocksync_enabled)
            _clk_cfg.set_rebase_enabled(cfg.rebase_clocks)
            # [memory] section: accounting default + report size; the
            # SCANNER_TPU_MEMSTATS* env vars (read at import) win
            from ..util import memstats
            if not os.environ.get("SCANNER_TPU_MEMSTATS"):
                memstats.set_enabled(cfg.memstats_enabled)
            memstats.set_report_top_n(cfg.memstats_report_top_n)
            # [perf] frame_cache_*: the paged HBM frame cache's
            # deployment defaults (SCANNER_TPU_FRAME_CACHE_MB, read at
            # import, wins over frame_cache_mb)
            from .framecache import (set_capacity_mb, set_enabled,
                                     set_page_frames)
            set_enabled(cfg.frame_cache_enabled)
            set_capacity_mb(cfg.frame_cache_mb)
            set_page_frames(cfg.frame_cache_page_frames)
            # [perf] fusion_*: whole-pipeline XLA fusion defaults
            from ..graph import fusion as _fusion_cfg
            _fusion_cfg.set_enabled(cfg.fusion_enabled)
            _fusion_cfg.set_min_chain(cfg.fusion_min_chain)
            # [alerts] section: health/SLO engine default + user rules;
            # the SCANNER_TPU_HEALTH env var (read at import) wins
            from ..util import health as _health_cfg
            if not os.environ.get("SCANNER_TPU_HEALTH"):
                _health_cfg.set_enabled(cfg.alerts_enabled)
            # [robustness] section: the master's write-ahead bulk
            # journal defaults; SCANNER_TPU_JOURNAL* env vars (read at
            # import) win per process
            from . import journal as _journal_cfg
            if not os.environ.get("SCANNER_TPU_JOURNAL"):
                _journal_cfg.set_enabled(cfg.journal_enabled)
            if not os.environ.get("SCANNER_TPU_JOURNAL_ROTATE"):
                _journal_cfg.set_rotate_records(
                    cfg.journal_rotate_records)
            # [gang] section: gang-scheduled multi-host execution
            # defaults; the SCANNER_TPU_GANG* env vars (read at
            # import) win per process
            from . import gang as _gang_cfg
            if not os.environ.get("SCANNER_TPU_GANG"):
                _gang_cfg.set_enabled(cfg.gang_enabled)
            if not os.environ.get("SCANNER_TPU_GANG_INIT_TIMEOUT"):
                _gang_cfg.set_init_timeout_s(cfg.gang_init_timeout_s)
            if not os.environ.get("SCANNER_TPU_GANG_FORM_TIMEOUT"):
                _gang_cfg.set_form_timeout_s(cfg.gang_form_timeout_s)
            if not os.environ.get("SCANNER_TPU_GANG_SHARDED"):
                _gang_cfg.set_sharded(cfg.gang_sharded)
            if not os.environ.get("SCANNER_TPU_GANG_HALO"):
                _gang_cfg.set_halo(cfg.gang_halo_exchange)
            # [control] section: how many master shards the control
            # plane runs ([control] shards); the
            # SCANNER_TPU_CONTROL_SHARDS env var (read at import) wins
            from . import shardmap as _shardmap_cfg
            if not os.environ.get("SCANNER_TPU_CONTROL_SHARDS"):
                _shardmap_cfg.set_num_shards(cfg.control_shards)
            # [remediation] section: the alert->action controller's
            # deployment defaults; SCANNER_TPU_REMEDIATION (read at
            # import) is the per-process kill switch and wins
            from . import controller as _ctrl_cfg
            if not os.environ.get("SCANNER_TPU_REMEDIATION"):
                _ctrl_cfg.set_enabled(cfg.remediation_enabled)
            _ctrl_cfg.set_dry_run(cfg.remediation_dry_run)
            _ctrl_cfg.set_autoscale_bounds(
                *cfg.remediation_autoscale_bounds)
            # applied in both directions (like [trace]): a config with
            # rules="" CLEARS user rules an earlier config installed —
            # removed rules' states resolve instead of firing forever
            _health_cfg.configure(cfg.alert_rules)
            # explicit argument beats config beats default
            storage_type = storage_type or cfg.storage_type
            if master is None:
                master = cfg.master_address
            if metrics_port is None:
                metrics_port = cfg.metrics_port
            # [faults] plan arms the chaos-injection registry for this
            # process (env var SCANNER_TPU_FAULTS, read at import time,
            # wins — it is the per-process override)
            if cfg.faults_plan and not os.environ.get("SCANNER_TPU_FAULTS"):
                from ..util import faults
                faults.install(cfg.faults_plan)
        # persistent XLA executable cache, placed from outside
        # (JAX_COMPILATION_CACHE_DIR) or at the fixed in-checkout default:
        # jobs re-load jitted kernel executables across processes
        from ..util.jaxenv import enable_compilation_cache
        enable_compilation_cache()
        storage_type = storage_type or "posix"
        if db_path is None and storage_type == "posix":
            db_path = os.path.expanduser("~/.scanner_tpu/db")
        self._db = Database(make_storage(storage_type, db_path=db_path,
                                         **(storage_options or {})))
        self._db.load_megafile()
        self._profiler = Profiler(node="client")
        self._job_profiles: Dict[int, List[Profiler]] = {}
        # job id -> {"trace_id", "bulk_id"} for Client.trace()
        self._job_traces: Dict[int, Dict[str, Any]] = {}
        self._next_job_id = 0
        self._master_address = master
        self._cluster = None
        if master is not None:
            try:
                from .service import ClusterClient
            except ImportError as e:
                raise ScannerException(
                    "cluster mode requires scanner_tpu.engine.service") \
                    from e
            self._cluster = ClusterClient(master, db=self._db, **kw)

        # live telemetry endpoint — strictly opt-in (Client(metrics_port=)
        # or the [network] metrics_port config knob); port 0 binds an
        # ephemeral port, see self._metrics_server.port
        self._metrics_server = None
        if metrics_port is not None:
            from ..util.metrics import MetricsServer
            from ..util import coststats as _coststats
            from ..util import health as _health_st
            from ..util import memstats as _memstats
            from . import controller as _ctrl_st
            from . import framecache as _framecache
            self._metrics_server = MetricsServer(
                port=metrics_port,
                statusz=lambda: {"role": "client",
                                 "master": self._master_address,
                                 "db": getattr(self._db.backend, "root",
                                               None),
                                 "health": _health_st.status_dict(),
                                 "memory": _memstats.status_dict(),
                                 "framecache":
                                     _framecache.status_dict(),
                                 "efficiency":
                                     _coststats.status_dict(),
                                 "remediation":
                                     _ctrl_st.status_dict()},
                healthz=lambda: {"role": "client"})

        self.ops = O.OpGenerator()
        self.streams = StreamsGenerator()
        self.io = IOGenerator(self)
        self.partitioner = TaskPartitioner()
        # None stays None here (the per-job resolution in run() reads
        # it); the long-lived executor itself just needs a concrete int
        self._pipeline_instances_arg = pipeline_instances
        self._executor = LocalExecutor(
            self._db, self._profiler,
            num_load_workers=num_load_workers,
            num_save_workers=num_save_workers,
            pipeline_instances=pipeline_instances or 1,
            decoder_threads=decoder_threads)
        # the evaluators of the graph a local run ran last: the next
        # run of the same graph takes them over instead of constructing
        # its own (engine/evaluate.py EvaluatorPool); closed by stop()
        self._evaluators = EvaluatorPool()
        # health/SLO engine (util/health.py): local-mode runs get the
        # same backpressure/latency judgment cluster nodes do; no-op
        # when SCANNER_TPU_HEALTH=0 / [alerts] enabled=false
        from ..util import health as _health
        _health.ensure_started()
        # remediation controller (engine/controller.py): local-mode
        # runs get the worker-local playbooks (frame-cache shrink,
        # ladder re-warm); no-op when SCANNER_TPU_REMEDIATION=0
        from . import controller as _ctrl
        _ctrl.ensure_started()

    # -- context manager ----------------------------------------------------

    def __enter__(self) -> "Client":
        return self

    def __exit__(self, *exc) -> None:
        self.stop()

    def stop(self) -> None:
        self._evaluators.close()
        if self._cluster is not None:
            self._cluster.close()
        if self._metrics_server is not None:
            self._metrics_server.stop()
            self._metrics_server = None

    # -- live telemetry -----------------------------------------------------

    def job_status(self, bulk_id: Optional[int] = None) -> Dict[str, Any]:
        """Cluster job status (GetJobStatus): live progress of the given
        (default: active) bulk, plus `num_workers` even when no bulk is
        active — lets tooling wait for worker registration.  Cluster
        mode only."""
        if self._cluster is None:
            raise ScannerException(
                "job_status requires cluster mode (Client(master=...))")
        return self._cluster.job_status(bulk_id)

    def metrics(self) -> Dict[str, Any]:
        """Live metrics snapshot.  Cluster mode: the master's aggregated
        cluster-wide view (master + every live worker, each sample
        node-labeled).  Local mode: this process's registry under
        node="client".  Render with
        scanner_tpu.util.metrics.render_prometheus, or read values
        directly (see docs/observability.md for the series catalog)."""
        if self._cluster is not None:
            return self._cluster.metrics()
        from ..util.metrics import merge_snapshots, registry
        return merge_snapshots({"client": registry().snapshot()})

    def health(self) -> Dict[str, Any]:
        """Cluster health roll-up (docs/observability.md §Health &
        SLOs).  Cluster mode: the master's GetHealth view — worst-of
        `ok|degraded|unhealthy` across master + every live worker,
        node-prefixed reason codes, and each node's firing alerts
        (`{"status", "reasons", "firing", "nodes"}`).  Local mode: this
        process's health engine in the same shape under
        nodes["client"]."""
        if self._cluster is not None:
            return self._cluster.health()
        from ..util import health as _health
        return _health.merge_status({"client": _health.status_dict()})

    def memory_report(self) -> Dict[str, Any]:
        """Memory forensics (docs/observability.md §Memory).  Cluster
        mode: the master's GetMemoryReport view — its live HBM/
        allocation-ledger snapshot plus every one-shot OOM report
        workers shipped (each naming the top ledger entries by bytes
        with their owning task and trace id).  Local mode: this
        process's memstats view and last OOM report, if any."""
        if self._cluster is not None:
            return self._cluster.memory_report()
        from ..util import memstats
        last = memstats.last_report()
        return {"memory": memstats.status_dict(),
                "reports": [last] if last else []}

    def compile_report(self) -> Dict[str, Any]:
        """Compute-efficiency report (docs/observability.md
        §Efficiency & Compilation).  Cluster mode: the master's
        GetCompileLedger view — per node, the bounded XLA compile
        ledger (op, device, bucket, compile seconds, persistent-cache
        hit|miss|uncached, executable size, analytical cost), its
        summary with the cache hit rate, and the per-(op, device,
        bucket) roofline table (achieved FLOP/s, achieved bytes/s,
        compute-vs-memory bound, EFF%).  Local mode: this process's
        view in the same shape under nodes["client"]."""
        if self._cluster is not None:
            return self._cluster.compile_report()
        from ..util import coststats
        return {"nodes": {"client": coststats.compile_report()}}

    def shutdown_cluster(self, workers: bool = True) -> int:
        """Remotely stop the cluster this client is attached to: the
        master forwards Shutdown to every registered worker (unless
        workers=False), then stops itself — blocking start_master /
        start_worker processes exit 0.  Returns the number of workers
        that acknowledged.  Cluster mode only."""
        if self._cluster is None:
            raise ScannerException(
                "shutdown_cluster requires cluster mode "
                "(Client(master=...))")
        return self._cluster.shutdown_cluster(workers=workers)

    # -- data management ----------------------------------------------------

    def ingest_videos(self, named_paths: Sequence, inplace: bool = False,
                      force: bool = False):
        """Ingest videos as tables; returns (descriptors, failures) where
        failures is [(path, reason)] — a corrupt file is reported, not
        raised, so it cannot abort a corpus ingest (reference
        client.py:965 / ingest.cpp:872 failed_videos)."""
        from ..video import ingest_videos
        return ingest_videos(self._db, named_paths, inplace=inplace,
                             force=force)

    def ingest_images(self, name: str, paths: Sequence[str]):
        from ..video.ingest import ingest_images
        return ingest_images(self._db, name, paths)

    def load_op(self, module_path: str, name: Optional[str] = None):
        """Load a user op library: a Python module whose import registers
        ops via @register_op (the TPU-native analogue of the reference's
        dynamic .so op libraries, client.py:514 load_op)."""
        import hashlib
        import importlib.util
        import sys
        real = os.path.realpath(module_path)
        # unique module name per file path: never collides with stdlib or
        # a second op library of the same basename
        digest = hashlib.md5(real.encode()).hexdigest()[:8]
        base = os.path.splitext(os.path.basename(real))[0]
        mod_name = name or f"scanner_tpu_userops_{base}_{digest}"
        if mod_name in sys.modules:
            return sys.modules[mod_name]
        spec = importlib.util.spec_from_file_location(mod_name, module_path)
        if spec is None or spec.loader is None:
            raise ScannerException(f"cannot load op module: {module_path}")
        mod = importlib.util.module_from_spec(spec)
        # register before exec so pickled objects from the module resolve
        # in other processes (workers load_op the same path)
        sys.modules[mod_name] = mod
        try:
            spec.loader.exec_module(mod)
        except Exception:
            sys.modules.pop(mod_name, None)
            raise
        return mod

    def batch_load(self, streams: Sequence, rows=None, workers: int = 4):
        """Load several streams concurrently (reference Client.batch_load,
        client.py:1270 multiprocessing pool -> thread pool here: the
        decode path releases the GIL in C)."""
        from concurrent.futures import ThreadPoolExecutor
        with ThreadPoolExecutor(max_workers=workers) as pool:
            return list(pool.map(
                lambda s: list(s.load(rows=rows)), streams))

    def new_table(self, name: str, columns: Sequence[str],
                  rows: Sequence[Sequence[bytes]],
                  overwrite: bool = False) -> Table:
        self._db.new_table(name, columns, rows, overwrite=overwrite)
        return Table(self._db, name)

    def table(self, name: str) -> Table:
        if not self._db.has_table(name):
            raise ScannerException(f"no such table: {name}")
        return Table(self._db, name)

    def has_table(self, name: str) -> bool:
        return self._db.has_table(name)

    def delete_table(self, name: str) -> None:
        self._db.delete_table(name)

    def summarize(self) -> str:
        lines = ["table                          rows  committed"]
        for name in self._db.list_tables():
            try:
                desc = self._db.table_descriptor(name)
                lines.append(f"{name:28} {desc.num_rows:6}  "
                             f"{self._db.table_is_committed(name)}")
            except Exception:
                lines.append(f"{name:28}      ?  ?")
        out = "\n".join(lines)
        print(out)
        return out

    # -- execution ----------------------------------------------------------

    def run(self, outputs: Union[O.OpNode, Sequence[O.OpNode]],
            perf_params: Optional[PerfParams] = None,
            cache_mode: CacheMode = CacheMode.Error,
            show_progress: bool = True,
            profiling: bool = True,
            task_timeout: float = 0.0,
            **kw) -> int:
        """Execute a job set; returns a job id usable with get_profile."""
        if isinstance(outputs, O.OpNode):
            outputs = [outputs]
        perf = perf_params or PerfParams.estimate()
        if task_timeout:
            perf.task_timeout = task_timeout
        job_id = self._next_job_id
        self._next_job_id += 1
        prof = Profiler(node=f"job{job_id}")
        if self._cluster is not None:
            # the job's root trace span: NewJob (and the status polls)
            # run under it, so the master admits the bulk with this
            # trace_id and every worker task span chains back here
            from ..util import tracing as _tr
            tracer = _tr.default_tracer()
            root = _tr.open_span(tracer, "job", mode="cluster")
            try:
                with _tr.use_span(tracer, root):
                    profs = self._cluster.run(outputs, perf, cache_mode,
                                              show_progress)
            finally:
                _tr.close_span(tracer, root)
                # contribute the root span so the master's assembled
                # trace is self-contained (scanner_trace --verify walks
                # every chain to the root without this process)
                if root is not None \
                        and self._cluster.last_bulk_id is not None:
                    self._cluster.ship_spans(
                        self._cluster.last_bulk_id,
                        tracer.spans_for_trace(root.trace_id))
            self._job_profiles[job_id] = profs
            self._job_traces[job_id] = {
                "trace_id": root.trace_id if root else None,
                "bulk_id": self._cluster.last_bulk_id}
            return job_id
        # gang mode needs a cluster to co-schedule across: a local
        # (in-process) run IS a single host, so gang_hosts degrades to
        # ordinary execution — the degenerate 1-host gang — instead of
        # erroring (the same graph runs either way)
        if int(getattr(perf, "gang_hosts", 0) or 0):
            import logging
            logging.getLogger("scanner_tpu.engine").info(
                "gang_hosts=%d requested on a local run: executing as "
                "a single-host job (gang scheduling needs "
                "Client(master=...))", perf.gang_hosts)
        # instance-count resolution: explicit kwarg > PerfParams >
        # explicit Client(pipeline_instances=) — any of which wins as
        # given, including 1 — and only a fully-unset count resolves to
        # one device-affine instance per local chip on multi-chip hosts
        # (engine/evaluate.py default_pipeline_instances)
        from .evaluate import default_pipeline_instances
        # the root of the job's profile: every second between here and
        # the return lies in one of its children (docs/profiling.md)
        with prof.span("run", level=0, job=job_id) as root:
            ex = LocalExecutor(
                self._db, prof,
                num_load_workers=self._executor.num_load_workers,
                num_save_workers=self._executor.num_save_workers,
                decoder_threads=self._executor.decoder_threads,
                pipeline_instances=kw.get(
                    "pipeline_instances",
                    default_pipeline_instances(
                        perf.pipeline_instances_per_node
                        or self._pipeline_instances_arg)),
                evaluators=self._evaluators)
            try:
                jobs = ex.run(outputs, perf, cache_mode=cache_mode,
                              show_progress=show_progress)
            except BaseException:
                # a run that raised leaves nothing kept
                self._evaluators.close()
                raise
            ran = [j for j in jobs if not j.skipped]
            root.args.update(
                tasks=sum(len(j.tasks) for j in ran),
                rows=sum(e - s for j in ran for s, e in j.tasks))
        self._job_profiles[job_id] = [prof]
        self._job_traces[job_id] = {"trace_id": ex.last_trace_id,
                                    "bulk_id": None}
        return job_id

    def load_frames(self, table: str, rows, column: str = "frame"):
        """Decode exact frames of a stored video stream (public accessor
        for the client-side read path, reference storage.py load)."""
        from ..video import load_frames
        return load_frames(self._db, table, rows, column)

    def get_profile(self, job_id: int) -> Profile:
        if job_id not in self._job_profiles:
            raise ScannerException(f"no profile for job {job_id}")
        return Profile(self._job_profiles[job_id])

    def trace(self, job_id: int, path: Optional[str] = None,
              raw_clocks: bool = False) -> str:
        """Write ONE merged cross-host Perfetto/Chrome trace for a
        finished job: the assembled span tree (client root → master
        scheduling → worker task → stage → op, all under the job's
        trace_id) plus any captured XLA device timelines — cluster
        profiles carry their device events inline, so remote chips'
        lanes survive the hop (util/jaxprof.py).  Remote spans arrive
        rebased onto master time via the per-node clock offsets
        (docs/observability.md §Cross-host time) unless raw_clocks=True
        keeps each host's uncorrected stamps.  Returns the path
        written.  Open in ui.perfetto.dev; `tools/scanner_trace.py` is
        the CLI flavor and adds straggler analytics."""
        from ..util import tracing as _tr
        info = self._job_traces.get(job_id)
        if info is None or not info.get("trace_id"):
            raise ScannerException(
                f"no trace for job {job_id} (was tracing disabled? "
                "SCANNER_TPU_TRACING / [trace] enabled)")
        if self._cluster is not None and info.get("bulk_id") is not None:
            reply = self._cluster.get_trace(info["bulk_id"],
                                            raw_clocks=raw_clocks)
            # the run already shipped this process's root span; merge
            # the flight recorder anyway (dedup by span id) in case
            # that best-effort ship was lost
            by_id = {d["span_id"]: d for d in reply.get("spans") or []}
            for d in _tr.default_tracer().spans_for_trace(
                    info["trace_id"]):
                by_id.setdefault(d["span_id"], d)
            spans = list(by_id.values())
        else:
            spans = _tr.default_tracer().spans_for_trace(info["trace_id"])
            # local spans come from the bounded flight recorder: a big
            # job can evict its own early spans (incl. the root) — say
            # so instead of writing a silently partial trace
            if not any(d["name"] == "job" for d in spans):
                import logging
                logging.getLogger("scanner_tpu.tracing").warning(
                    "trace for job %d is partial: the flight recorder "
                    "(SCANNER_TPU_TRACE_RING) evicted its earliest "
                    "spans, including the root", job_id)
        from ..util.jaxprof import DEVICE_PID_BASE, load_device_events
        dev: List[Dict[str, Any]] = []
        base = DEVICE_PID_BASE
        for p in self._job_profiles.get(job_id, []):
            for rec in getattr(p, "device_traces", []):
                got = load_device_events(rec, pid_base=base)
                dev.extend(got)
                if got:
                    base += 1000
        path = path or f"scanner_trace_job{job_id}.json"
        return _tr.write_chrome_trace(spans, path, device_events=dev)

    def stragglers(self, job_id: int) -> Dict[str, Any]:
        """Straggler analytics for a job: per-stage span stats + the
        top-N slowest tasks with their trace ids.  Cluster mode reads
        the master's incrementally-maintained summary (also on
        GetJobStatus and /statusz); local mode computes it from this
        process's flight recorder."""
        from ..util import tracing as _tr
        info = self._job_traces.get(job_id)
        if info is None or not info.get("trace_id"):
            raise ScannerException(f"no trace for job {job_id}")
        if self._cluster is not None and info.get("bulk_id") is not None:
            reply = self._cluster.get_trace(info["bulk_id"])
            return reply.get("stragglers") or {}
        return _tr.straggler_summary(
            _tr.default_tracer().spans_for_trace(info["trace_id"]))
