"""Single-node pipelined job executor.

Capability parity: reference worker-side pipeline (worker.cpp:1467-1724
thread spawn; load_worker/evaluate_worker/save_worker stage drivers) minus
the RPC shell, which engine/service.py adds for the distributed path.

Stages, connected by bounded queues (reference runtime.h:81-90):

    task list -> [loader xL] -> [evaluator xP] -> [saver xS] -> commit

Loaders read item bytes / decode exact frame sets (C++ releases the GIL, so
loader threads overlap evaluator Python/JAX time).  Each evaluator thread is
one pipeline instance owning its kernel set.  Savers H.264-encode video
outputs and write column items.  Tasks are self-contained (warmup rows are
re-derived per task), so any instance may take any task.
"""

from __future__ import annotations

import bisect
import queue
import struct
import threading
import time
from collections import deque
from dataclasses import dataclass, field
from typing import Any, Dict, List, Optional, Sequence, Tuple

import numpy as np

from ..common import (CacheMode, DeviceType, JobException, NullElement,
                      PerfParams, ScannerException)
from ..graph import analysis as A
from ..graph import ops as O
from ..storage import Database
from ..storage import items as IT
from ..storage import metadata as md
from ..storage.streams import NamedVideoStream, StoredStream
from ..util import faults as _faults
from ..util import metrics as _mx
from ..util import tracing as _tr
from ..util.log import get_logger
from ..util.profiler import Profiler
from . import framecache as _fc
from .batch import ColumnBatch, concat_batches
from .evaluate import EvaluatorPool, TaskEvaluator

# live pipeline telemetry (docs/observability.md).  Queue depths answer
# the round-3 attribution question ("which stage starves?") in real
# time: a full evaluate queue + idle save queue = compute-bound, etc.
_M_QDEPTH = _mx.registry().gauge(
    "scanner_tpu_stage_queue_depth",
    "Tasks currently queued ahead of a pipeline stage and waiting on "
    "that stage alone (live; sampled at scrape time from the bounded "
    "inter-stage queues).  A streaming task is queued for evaluate "
    "before its chunks are decoded: it counts once its loader has "
    "decoded as far ahead as the task's chunk queue lets it.",
    labels=["stage"])
_M_STAGE_SECONDS = _mx.registry().counter(
    "scanner_tpu_stage_seconds_total",
    "Wall seconds spent in each pipeline stage across all stage threads.",
    labels=["stage"])
_M_STAGE_TASKS = _mx.registry().counter(
    "scanner_tpu_stage_tasks_total",
    "Tasks completed per pipeline stage.",
    labels=["stage"])
_M_CHUNK_WAIT = _mx.registry().counter(
    "scanner_tpu_chunk_wait_seconds_total",
    "Evaluator seconds spent waiting on loader chunk production "
    "(work-packet streaming starvation; mirrors evaluate:chunk_wait "
    "trace intervals).")
_M_DECODED = _mx.registry().counter(
    "scanner_tpu_decoded_frames_total",
    "Video frames delivered to the pipeline by the decoder (the rows "
    "asked of it), per loader thread.  What the codec decoded to get "
    "them is scanner_tpu_codec_frames_total.",
    labels=["loader"])
_M_CODEC_FRAMES = _mx.registry().counter(
    "scanner_tpu_codec_frames_total",
    "Video frames the codec decoded, delivered or dropped: every frame "
    "of each keyframe-aligned decode run, per loader thread.  Over "
    "scanner_tpu_decoded_frames_total it is the codec's work per "
    "delivered frame (1 for a dense scan of whole GOPs).",
    labels=["loader"])
_M_DECODE_SECONDS = _mx.registry().counter(
    "scanner_tpu_decode_seconds_total",
    "Seconds spent decoding video frames, per loader thread.",
    labels=["loader"])
# per-chip utilization under evaluator affinity: every chip of a
# multi-device host should take tasks and accumulate open seconds; a
# chip stuck at zero while siblings climb = an instance wedged or an
# assignment bug ("default" = affinity off / single device)
_M_DEV_TASKS = _mx.registry().counter(
    "scanner_tpu_device_tasks_total",
    "Tasks evaluated per assigned device (pipeline-instance affinity: "
    "instance i stages and runs on chip i mod n_devices).",
    labels=["device"])
_M_EVAL_OPEN = _mx.registry().counter(
    "scanner_tpu_evaluate_open_seconds_total",
    "Evaluate-stage wall seconds per assigned device, accrued while a "
    "task runs (overlapping tasks on one device count once): the rate "
    "over any window is the share of it an evaluate task was open on "
    "the chip, never more than 1.  Host time, not the device's: compile "
    "and chunk-wait time inside a task count.",
    labels=["device"])
# the run lifecycle on the client thread and the waits of the stage
# threads, each recorded with the profiler span of the same name at the
# same two clock reads (docs/profiling.md has the span tree)
_M_RUNS = _mx.registry().counter(
    "scanner_tpu_runs_total",
    "Local runs (LocalExecutor.run) begun in this process.")
_M_RUN_SECONDS = _mx.registry().counter(
    "scanner_tpu_run_seconds_total",
    "Client-thread seconds of local runs by phase: prepare (graph "
    "analysis, output tables, task list), pipeline (stage threads' "
    "start to last join), drain (last save's end to the last saver's "
    "join; inside pipeline), commit (table commits, megafile).",
    labels=["phase"])
# the parts of run:prepare and run:commit, each the child span of its
# name (prepare:analyze, prepare:jobs, commit:tables, commit:sinks,
# commit:megafile); scanner_tpu_run_seconds_total keeps the phases whole
_M_RUN_PART_SECONDS = _mx.registry().counter(
    "scanner_tpu_run_part_seconds_total",
    "Client-thread seconds of local runs by part of a phase: analyze "
    "(graph analysis, perf estimate) and jobs (sources resolved, tasks "
    "cut, output tables created) of prepare; tables (a commit_table a "
    "sink table, each a whole save of the database's metadata), sinks "
    "(custom sinks' finished) and megafile of commit.",
    labels=["part"])
_M_MEGAFILE_BYTES = _mx.registry().counter(
    "scanner_tpu_megafile_bytes_total",
    "Bytes of the table megafile written at the end of local runs: the "
    "descriptor of every committed table of the database, packed anew "
    "by each run.")
_M_EVAL_SETUP_SECONDS = _mx.registry().counter(
    "scanner_tpu_evaluator_setup_seconds_total",
    "Evaluator-thread seconds spent opening evaluators: constructing "
    "one (kernel construction, fetch_resources, set-up, weight "
    "restore) or adopting a kept one.")
_M_EVAL_SETUPS = _mx.registry().counter(
    "scanner_tpu_evaluator_setups_total",
    "Evaluators opened, constructed or reused: one per pipeline "
    "instance per run.")
_M_EVAL_REUSES = _mx.registry().counter(
    "scanner_tpu_evaluator_reuses_total",
    "Evaluators a run was handed by its owner's EvaluatorPool instead "
    "of constructing them: the graph that ran last, run again.")
_M_LOAD_WORKERS = _mx.registry().gauge(
    "scanner_tpu_load_workers",
    "Loader threads of the pipeline run that started last: the count "
    "given, or the one derived from usable cores, evaluator instances, "
    "tasks and, where tasks load whole, queue depth (evaluate.py "
    "default_load_workers).")
_M_RUN_LOADERS = _mx.registry().counter(
    "scanner_tpu_run_loaders_total",
    "Loader threads that local runs started, summed over runs: over "
    "scanner_tpu_runs_total, the width of a run's load stage.")
_M_STAGE_WAIT = _mx.registry().counter(
    "scanner_tpu_stage_wait_seconds_total",
    "Seconds a stage thread waited on a neighbor: load = blocked "
    "putting into a full evaluate or chunk queue, evaluate = waiting "
    "for a task (chunk waits are scanner_tpu_chunk_wait_seconds_total), "
    "evaluate_out = blocked handing an evaluated task to a full save "
    "queue, save = waiting for an evaluated task.  A stage thread's "
    "last wait, which ends with its queue closed, counts.",
    labels=["stage"])
_M_WAIT_LOAD, _M_WAIT_EVAL, _M_WAIT_EVAL_OUT, _M_WAIT_SAVE = (
    _M_STAGE_WAIT.labels(stage=st)
    for st in ("load", "evaluate", "evaluate_out", "save"))
# the load stage's parts beside the decode, each the child span of its
# name inside `load` (load:open, load:assemble, load:stage,
# load:prestage); what is left of scanner_tpu_stage_seconds_total
# {stage="load"} less these and the decode is the stage's self time
_M_LOAD_PART_SECONDS = _mx.registry().counter(
    "scanner_tpu_load_part_seconds_total",
    "Loader seconds by part of the load stage: open (a streaming "
    "task's feeds made: the frame cache consulted and pinned, the "
    "decoder handle opened), assemble (a chunk's host assembly beside "
    "the decode that fills it: the array allocated, the rows held from "
    "an earlier chunk copied in, a row moved to its slot), stage (the "
    "frame cache's assembly: fresh rows staged and offered, resident "
    "rows gathered; the dispatch, not the copy's completion), prestage "
    "(device_put of the device-bound columns still on the host).",
    labels=["part"])
_M_LOAD_OPEN, _M_LOAD_ASSEMBLE, _M_LOAD_STAGE, _M_LOAD_PRESTAGE = (
    _M_LOAD_PART_SECONDS.labels(part=p)
    for p in ("open", "assemble", "stage", "prestage"))
_M_LOAD_ASSEMBLED = _mx.registry().counter(
    "scanner_tpu_load_assembled_rows_total",
    "Decoded rows of streaming chunks by how each came to lie in the "
    "array its chunk is staged from: direct (the codec wrote it into "
    "its slot), carried (copied from an earlier chunk's array: a "
    "stencil's back-reach), moved (decoded off its slot and copied to "
    "it: an open-GOP head delivered late, a chunk that skips rows a "
    "later one wants).",
    labels=["how"])
# the evaluate stage's parts that belong to no op (evaluate:merge,
# evaluate:prefetch, inside the task's `evaluate` span)
_M_EVAL_PART_SECONDS = _mx.registry().counter(
    "scanner_tpu_evaluate_part_seconds_total",
    "Evaluator seconds by part of the evaluate stage outside every op: "
    "merge (a streaming task's chunk results joined per sink) and "
    "prefetch (the sinks' device->host copies started at eval-done).",
    labels=["part"])
# the save stage's parts, each recorded with the profiler span of the
# same name at the same two clock reads: the fetch of a sink's batch
# (save:fetch) and the encode of a frame column's item (save:encode,
# inside save:write; what is left of save:write is the storage write)
_M_SINK_FETCH_SECONDS = _mx.registry().counter(
    "scanner_tpu_sink_fetch_seconds_total",
    "Saver seconds spent bringing sinks' batches to the host and "
    "taking their rows (span save:fetch): the blocking end of the "
    "device->host copy started at eval-done.")
_M_SINK_FETCH_BYTES = _mx.registry().counter(
    "scanner_tpu_sink_fetch_bytes_total",
    "Bytes of sinks' batches brought from a device to the host by the "
    "save stage (a batch already on the host counts nothing).")
_M_SINK_ROWS = _mx.registry().counter(
    "scanner_tpu_sink_rows_total",
    "Rows of sinks' batches that were on a device when evaluation was "
    "done, by how they reach the host: relaid (the chip held them off "
    "row-major, planar frames, and one device program laid them out "
    "row-major before the copy), asis (fetched in the layout they had: "
    "row-major already, or a wire format).  A batch already on the "
    "host counts nothing.",
    labels=["layout"])
# a frame column that is not uint8 RGB (float32 flow fields) is not
# video: its rows are pickled one by one into a blob item (save:raw,
# inside save:write)
_M_RAW_FRAME_SECONDS = _mx.registry().counter(
    "scanner_tpu_raw_frame_seconds_total",
    "Saver seconds spent writing items of frame columns that are not "
    "video (span save:raw): a pickle of each row's array, the item's "
    "build (sizes, checksum, one joined buffer) and the backend write.")
_M_RAW_FRAME_PART_SECONDS = _mx.registry().counter(
    "scanner_tpu_raw_frame_part_seconds_total",
    "Saver seconds by part of save:raw (spans raw:pickle, raw:build, "
    "raw:write): the pickle of each row's array, the item's build "
    "(sizes, checksum over every byte, one joined buffer) and the "
    "backend's write and fsync.",
    labels=["part"])
_M_RAW_PICKLE, _M_RAW_BUILD, _M_RAW_WRITE = (
    _M_RAW_FRAME_PART_SECONDS.labels(part=p)
    for p in ("pickle", "build", "write"))
_M_RAW_FRAME_BYTES = _mx.registry().counter(
    "scanner_tpu_raw_frame_bytes_total",
    "Bytes of the items written for frame columns that are not video, "
    "header and pickle framing included.")
_M_ENCODE_SECONDS = _mx.registry().counter(
    "scanner_tpu_encode_seconds_total",
    "Saver seconds spent encoding frame columns to video (span "
    "save:encode): the encoder's creation, feeds, flush and the take "
    "of its packets, one encoder an item.")
_M_CONTIGUOUS_SECONDS = _mx.registry().counter(
    "scanner_tpu_save_contiguous_seconds_total",
    "Saver seconds spent making a video item's rows contiguous uint8 "
    "frames, each just before the encoder is fed it: inside "
    "scanner_tpu_encode_seconds_total and the save:encode span, whose "
    "`contiguous_s` arg is an item's share (a row fetched from a chip "
    "comes strided as the chip laid it out).")
_M_ENCODED_FRAMES = _mx.registry().counter(
    "scanner_tpu_encoded_frames_total",
    "Frames the save stage encoded to video.")
_M_ENCODED_BYTES = _mx.registry().counter(
    "scanner_tpu_encoded_bytes_total",
    "Bytes of encoded video the save stage produced (packet data, "
    "before the item's index).")
# end-to-end per-task latency: enqueue (task runnable — local admission
# or master bulk admission) to sink-committed.  The seed for
# serving-mode p50/p99 (ROADMAP item 2): under a request-shaped
# workload each "task" is a request and this histogram IS the latency
# SLO series.  Observed by the committing side only — the local saver,
# or the master at FinishedWork — so cluster runs never double-count.
_M_TASK_LATENCY = _mx.registry().histogram(
    "scanner_tpu_task_latency_seconds",
    "End-to-end per-task latency from enqueue to sink-committed "
    "(local: admission to save completion; cluster: bulk admission to "
    "FinishedWork, observed on the master).",
    buckets=(0.05, 0.1, 0.25, 0.5, 1.0, 2.5, 5.0, 10.0, 30.0, 60.0,
             120.0, 300.0, 600.0))

# a wait shorter than this leaves no interval (its counter still counts)
_WAIT_SPAN_MIN_S = 0.005

_CHUNK_DONE = object()   # streaming producer: all chunks delivered
_CHUNK_ERR = object()    # streaming producer: (marker, exception)

_log = get_logger("engine")


class _StageQueue:
    """Bounded hand-off between two pipeline stages that ends when it is
    told to, not when a waiter times out.  The side that fills it calls
    `close()` once its last producer has finished; consumers then take
    what is left and get None.  `abort()` is the error path: every
    blocked `put` and `get` returns at once, and what is queued is left
    where it is.  `bounded` names the items the bound counts (all of
    them where it is None): any other is queued past it, in its turn."""

    def __init__(self, maxsize: int, bounded=None):
        self._cond = threading.Condition()
        self._items: deque = deque()
        self._maxsize = maxsize
        self._bounded = bounded or (lambda item: True)
        self._closed = False
        self._aborted = False

    def qsize(self) -> int:
        return len(self._items)

    def count(self, pred) -> int:
        """Queued items that `pred` holds for."""
        with self._cond:
            return sum(1 for item in self._items if pred(item))

    def _full(self) -> bool:
        return sum(map(self._bounded, self._items)) >= self._maxsize

    def put(self, item) -> bool:
        """Blocks while full, if the bound counts `item`.  False, and
        the item not queued, once the queue is aborted."""
        with self._cond:
            if self._bounded(item):
                while self._full() and not self._aborted:
                    self._cond.wait()
            if self._aborted:
                return False
            self._items.append(item)
            # producers and consumers share the condition: wake them all
            self._cond.notify_all()
            return True

    def get(self):
        """Blocks while empty and open.  None once the queue is closed
        and empty, or aborted."""
        with self._cond:
            while not (self._items or self._closed or self._aborted):
                self._cond.wait()
            if self._aborted or not self._items:
                return None
            item = self._items.popleft()
            self._cond.notify_all()
            return item

    def close(self) -> None:
        with self._cond:
            self._closed = True
            self._cond.notify_all()

    def abort(self) -> None:
        with self._cond:
            self._aborted = True
            self._cond.notify_all()


@dataclass
class JobContext:
    job_idx: int
    jr: A.JobRows
    tasks: List[Tuple[int, int]]
    # per Input node: metadata for loading
    source_info: Dict[int, Dict[str, Any]]
    # per sink node id: (table descriptor, column name, codec, encode opts)
    sink_tables: Dict[int, Tuple[md.TableDescriptor, str, str, Dict]]
    fps: float = 30.0
    skipped: bool = False
    tasks_done: int = 0
    # sparse-read crossover for column loads (PerfParams
    # .load_sparsity_threshold -> items.read_item_rows)
    sparsity_threshold: int = 8
    # per sink id: "video" | "pickle", fixed by the first task written so
    # mixed-dtype frame outputs fail loudly instead of corrupting the table
    sink_modes: Dict[int, str] = field(default_factory=dict)
    sink_mode_lock: threading.Lock = field(default_factory=threading.Lock)
    # sinks writing through a CustomStorage instead of the database
    custom_sinks: Dict[int, Any] = field(default_factory=dict)


@dataclass
class TaskItem:
    job: JobContext
    task_idx: int
    output_range: Tuple[int, int]
    plan: Optional[A.TaskPlan] = None
    elements: Optional[Dict[int, Any]] = None
    results: Optional[Dict[int, Any]] = None
    # master-assigned attempt id (cluster mode): distinguishes re-issues
    # of the same task after a timeout revocation
    attempt: int = 0
    # distributed tracing (util/tracing.py): the parent context this
    # task's span attaches under (local: the job root span; cluster: the
    # master's assign span from the NextWork reply), and the open task
    # span itself — created by the loader, resumed by each stage thread,
    # closed after save/failure
    trace_ctx: Optional[Any] = None
    trace_span: Optional[Any] = None
    # when this task became runnable; 0 = unknown (cluster workers leave
    # it unset: the master observes end-to-end latency there)
    enqueued_at: float = 0.0
    # device affinity: the pipeline instance this task was assigned to at
    # enqueue time and that instance's chip — recorded BEFORE loading so
    # the loader's device staging targets the chip that will actually
    # evaluate the task (a mismatch would silently copy cross-chip)
    instance: int = 0
    device: Optional[Any] = None
    # work-packet streaming (PerfParams.stream_work_packets): the task's
    # per-chunk plans, the loader->evaluator chunk queue, and the abort
    # handshake (evaluator failure must unblock a producing loader)
    chunk_plans: Optional[List[A.TaskPlan]] = None
    chunk_q: Optional["queue.Queue"] = None
    chunk_abort: Optional[threading.Event] = None
    # frame-cache page leases (engine/framecache.py): pages this task
    # gathers from stay pinned — ineligible for eviction — until
    # evaluation finishes (released by the executor; a finalizer on
    # this TaskItem is the abort backstop)
    cache_leases: Optional[List[Any]] = None
    # sharded gang members (engine/gang.py): per source node, the global
    # rows this member does NOT decode — its neighbors own them and the
    # post-load halo exchange delivers them over the mesh; halo_fill is
    # the hook that runs that exchange and splices the received rows
    # into the loaded elements before device prestaging
    halo_drop: Optional[Dict[int, Any]] = None
    halo_fill: Optional[Any] = None
    # rows the loader actually decoded/read for this task (set by
    # _load_task after any halo restriction) — the per-member decode
    # accounting the sharded-gang metrics report
    decode_rows: int = 0


def _loaded_whole(w: TaskItem) -> bool:
    """What the evaluate queue's bound is there to limit: a task whose
    every source row is loaded before it is queued.  A streaming task
    holds its own `chunk_q` of two chunks and no more, so it takes no
    place in that bound: its loader queues it and decodes at once, and
    the load stage is as wide as the host's spare cores
    (evaluate.py default_load_workers)."""
    return w.chunk_q is None


def _awaits_evaluator(w: TaskItem) -> bool:
    """A queued task that waits on the evaluator alone: loaded whole,
    or a streaming task whose loader has filled its chunk queue.  One
    whose chunks are still being decoded is in the evaluate queue only
    so that they can stream: a wide load stage keeps that queue full
    of such tasks while the evaluator starves."""
    return _loaded_whole(w) or w.chunk_q.full()


class _StatefulChain:
    """Per-job planning chain for stateful task affinity
    (PerfParams.stateful_task_affinity; reference save_coordinator
    worker.cpp:373-415 packet pinning).

    Loaders plan a chained job's tasks in task order: `gate_plan` waits
    (briefly) until the preceding task was planned, then hands back the
    watermark map — the row each unbounded-state kernel's state will
    have advanced through — so analysis derives an incremental plan.
    The gate orders only the cheap PLAN step; decode still runs on all
    loader threads concurrently.  A timeout (a failed or reordered
    predecessor) degrades that one task to the self-contained plan;
    the chain then continues from its watermarks.  Correctness never
    depends on any of this: the evaluator re-verifies the premise
    against actual kernel state (StateCarryMiss -> self-contained
    re-run)."""

    GATE_TIMEOUT = 5.0

    def __init__(self):
        self.cond = threading.Condition()
        self.last_planned: Optional[int] = None
        # (unbounded node id, slice group) -> last row planned through
        self.water: Dict[Tuple[int, int], int] = {}

    def gate_plan(self, task_idx: int) -> Optional[Dict[Tuple[int, int],
                                                        int]]:
        """Block until `task_idx` is next in the chain (or timeout);
        returns the carry map, or None for a self-contained plan."""
        with self.cond:
            deadline = time.time() + self.GATE_TIMEOUT
            while self.last_planned is not None \
                    and task_idx > self.last_planned + 1:
                left = deadline - time.time()
                if left <= 0 or not self.cond.wait(timeout=left):
                    if deadline - time.time() <= 0:
                        break
            if self.last_planned is None \
                    or task_idx == self.last_planned + 1:
                return dict(self.water)
            return None

    def planned(self, task_idx: int,
                watermarks: Dict[Tuple[int, int], int]) -> None:
        with self.cond:
            if self.last_planned is None or task_idx > self.last_planned:
                self.last_planned = task_idx
            for k, m in watermarks.items():
                if m > self.water.get(k, -1):
                    self.water[k] = m
            self.cond.notify_all()


class LocalExecutor:
    def __init__(self, db: Database, profiler: Optional[Profiler] = None,
                 num_load_workers: Optional[int] = None,
                 num_save_workers: int = 2,
                 pipeline_instances: int = 1, node_id: int = 0,
                 decoder_threads: int = 1,
                 evaluators: Optional[EvaluatorPool] = None):
        self.db = db
        self.profiler = profiler or Profiler()
        # who keeps this executor's evaluators between runs (a Client,
        # a Worker); None: each is made for its run and closed with it
        self.evaluators = evaluators
        # None = derived at each run (evaluate.py default_load_workers)
        self.num_load_workers = num_load_workers
        # (loader threads, evaluator instances, saver threads) the last
        # run_pipeline started; None until one has
        self.stage_widths: Optional[Tuple[int, int, int]] = None
        self.num_save_workers = num_save_workers
        self.pipeline_instances = pipeline_instances
        self.node_id = node_id
        # libav threads per decoder handle (frame threading); total decode
        # parallelism = num_load_workers x decoder_threads
        self.decoder_threads = decoder_threads
        # per-graph memo for _column_device_bound (keyed by GraphInfo
        # identity; cleared when a different graph runs).  Locked: loader
        # threads share it and a concurrent clear() mid-read would KeyError
        self._device_bound_cache: Dict[Any, Any] = {}
        self._device_bound_lock = threading.Lock()
        # job idx -> _StatefulChain when stateful task affinity is active
        self._chains: Dict[int, _StatefulChain] = {}
        # PerfParams.stream_work_packets, latched per run/bulk
        self._stream_opt = True
        # span sink for this executor's task/stage/op spans; a cluster
        # Worker swaps in its own export-enabled tracer so spans ship to
        # the master (ShipSpans)
        self.tracer = _tr.default_tracer()
        # trace_id of the last local run (Client.trace reads it)
        self.last_trace_id: Optional[str] = None
        # when the pipeline's savers last finished a task (run:drain
        # starts there); None until one has
        self._last_save_end: Optional[float] = None
        self._save_end_lock = threading.Lock()
        # frame-cache source identity: table ids are per-database and
        # restart at 0 (and a database re-created at the same root
        # would restart them too), so pages are keyed under a
        # per-backend-object (root, seq) identity — no two Database
        # objects in one process can ever alias each other's pages
        # (engine/framecache.py db_cache_key)
        self._cache_db_key = _fc.db_cache_key(db.backend)

    # ------------------------------------------------------------------
    # Job-set preparation (reference master.cpp:1367 process_job admission)
    # ------------------------------------------------------------------

    def prepare(self, outputs: Sequence[O.OpNode], perf: PerfParams,
                cache_mode: CacheMode = CacheMode.Error
                ) -> Tuple[A.GraphInfo, List[JobContext]]:
        prof = self.profiler
        with prof.span("prepare:analyze",
                       counter=_M_RUN_PART_SECONDS.labels(part="analyze")):
            info = A.analyze(outputs)
            perf = self._estimate_perf(info, perf)
        with prof.span("prepare:jobs", jobs=info.num_jobs,
                       counter=_M_RUN_PART_SECONDS.labels(part="jobs")
                       ) as span:
            jobs = [self._prepare_job(info, j, perf, cache_mode)
                    for j in range(info.num_jobs)]
            span.args["tasks"] = sum(len(job.tasks) for job in jobs)
        return info, jobs

    def prepare_readonly(self, outputs: Sequence[O.OpNode], perf: PerfParams
                         ) -> Tuple[A.GraphInfo, List[JobContext]]:
        """Worker-side preparation: identical analysis but output tables
        were already created by the master — look them up instead of
        creating (reference workers re-run DAG analysis, worker.cpp:1013)."""
        info = A.analyze(outputs)
        perf = self._estimate_perf(info, perf)
        jobs: List[JobContext] = []
        for j in range(info.num_jobs):
            jobs.append(self._prepare_job(info, j, perf,
                                          CacheMode.Overwrite,
                                          create_tables=False))
        return info, jobs

    def _bind_if_unbound(self, stream) -> None:
        """Re-bind a stream that traveled over RPC: __getstate__ nulls its
        client (streams.py), so `_sc is None` — distinct from a missing
        attribute (non-stream objects) — means 'needs this executor's
        db'."""
        if getattr(stream, "_sc", False) is None:
            stream.bind(self.db)

    def _estimate_perf(self, info: A.GraphInfo, perf: PerfParams
                       ) -> PerfParams:
        if not getattr(perf, "_estimate", False):
            if perf.io_packet_size % perf.work_packet_size != 0:
                raise ScannerException(
                    "io_packet_size must be a multiple of work_packet_size")
            return perf
        # geometry-aware sizing (the reference's PerfParams.estimate
        # analog, common.py:78-160): target ~64 MB of decoded frames per
        # io packet so tasks neither thrash tiny items nor blow host RAM
        frame_bytes = 0
        keyint = 0
        for n in info.sources:
            for s in n.extra["streams"]:
                self._bind_if_unbound(s)
                if getattr(s, "is_video", False) \
                        and hasattr(s, "estimate_geometry"):
                    # real errors (bad path, storage failure) propagate:
                    # silently mis-sizing a 4K stream as VGA would blow
                    # host RAM far from the actual cause
                    fb, ki = s.estimate_geometry()
                    frame_bytes = max(frame_bytes, fb)
                    keyint = max(keyint, ki)
                elif getattr(s, "is_video", False) \
                        and hasattr(s, "estimate_size"):
                    frame_bytes = max(frame_bytes, s.estimate_size())
        if frame_bytes > 0:
            target = 64 << 20
            io = max(16, min(512, target // frame_bytes))

            def best_work(n: int):
                """Best divisor of n in [4, 16] (compute batch floor:
                1-row work packets drown in scheduling overhead).
                Powers of two are preferred so steady-state work packets
                land exactly on a bucket of the shape-stable kernel
                dispatch (engine/evaluate.py bucket_ladder) — a full
                chunk then never pads."""
                for w in (16, 8, 4):
                    if n % w == 0:
                        return w
                for w in range(min(16, n), 3, -1):
                    if n % w == 0:
                        return w
                return None

            # snap io packets to a multiple of the keyframe interval so
            # task boundaries land on keyframes: a mid-GOP task start
            # re-decodes the GOP prefix (up to keyint-1 frames) for
            # nothing.  The snap is dropped rather than accepted when it
            # would cross the 16-frame floor (round up instead) or leave
            # no workable packet divisor.
            work = None
            if keyint > 1 and keyint <= 2 * io:
                snapped = (io // keyint) * keyint
                if snapped < 16:
                    snapped += keyint
                w = best_work(snapped)
                if w is not None:
                    io, work = snapped, w
            if work is None:
                # round down to a power of two: the work packet is the
                # kernel call shape, and a pow2 packet is its own bucket
                work = max(4, min(16, io // 4))
                work = 1 << (int(work).bit_length() - 1)
                io = (io // work) * work
            perf.io_packet_size = int(io)
            perf.work_packet_size = int(work)
        else:
            perf.io_packet_size = 512
            perf.work_packet_size = 128
        # resolution happens exactly once: cluster workers receive the
        # concrete sizes and must not re-estimate (estimate_size does I/O
        # and could diverge from the master's task partitioning)
        perf._estimate = False  # type: ignore[attr-defined]
        return perf

    def _prepare_job(self, info: A.GraphInfo, j: int, perf: PerfParams,
                     cache_mode: CacheMode,
                     create_tables: bool = True) -> JobContext:
        # resolve sources
        source_info: Dict[int, Dict[str, Any]] = {}
        source_rows: Dict[int, int] = {}
        fps = 30.0
        for n in info.sources:
            stream: StoredStream = n.extra["streams"][j]
            self._bind_if_unbound(stream)
            if getattr(stream, "is_custom", False):
                # pluggable source (reference Source::read extension point)
                source_info[n.id] = {"custom": stream, "is_video": False}
                source_rows[n.id] = stream.len()
                continue
            if isinstance(stream, NamedVideoStream):
                stream.ensure_ingested()
            if not stream.committed():
                raise JobException(
                    f"input stream {stream.name} does not exist or is "
                    f"not committed")
            desc = self.db.table_descriptor(stream.name)
            col = stream.column if stream.column in desc.column_names() \
                else next(c for c in desc.column_names() if c != "index")
            is_video = desc.column_type(col) == md.ColumnType.VIDEO
            vinfo = None
            if is_video:
                from ..video import load_video_meta
                vinfo = load_video_meta(self.db, stream.name, col)
                if vinfo.fps:
                    fps = vinfo.fps
            codec = next((c.codec for c in desc.columns if c.name == col),
                         "raw")
            source_info[n.id] = {
                "table": desc, "column": col, "is_video": is_video,
                "video_meta": vinfo, "codec": codec,
            }
            source_rows[n.id] = desc.num_rows

        jr = A.job_rows(info, j, source_rows)
        jr.work_packet_size = int(perf.work_packet_size)
        tasks = A.generate_tasks(jr, perf.io_packet_size)

        # output tables (pre-created uncommitted, reference
        # master.cpp:1619-1663).  CacheMode.Ignore skips the job only when
        # EVERY sink output already exists committed (job-level resume,
        # reference client.py:1389-1430)
        custom_sinks: Dict[int, Any] = {}
        sink_names = []
        table_sinks = []
        for sink in info.sinks:
            out_stream = sink.extra["streams"][j]
            self._bind_if_unbound(out_stream)
            if getattr(out_stream, "is_custom", False):
                # CacheMode applies to custom sinks too: stale rows from a
                # previous (longer) run must not survive an Overwrite
                if create_tables and out_stream.storage.exists(out_stream):
                    if cache_mode == CacheMode.Error:
                        raise JobException(
                            f"custom output {out_stream.name} already "
                            f"exists (pass cache_mode=CacheMode.Overwrite)")
                    if cache_mode == CacheMode.Overwrite:
                        out_stream.storage.delete_stream(out_stream)
                custom_sinks[sink.id] = out_stream
                continue
            table_sinks.append(sink)
            sink_names.append(out_stream.name if hasattr(out_stream, "name")
                              else str(out_stream))
        if not create_tables:
            sink_tables = {}
            for sink, name in zip(table_sinks, sink_names):
                if not self.db.has_table(name):
                    continue  # job skipped by the master
                src_col = sink.input_columns()[0]
                codec = self._codec_for(src_col)
                desc = self.db.table_descriptor(name)
                enc = dict(sink.extra.get("encode_options") or {})
                sink_tables[sink.id] = (desc, desc.columns[0].name, codec,
                                        enc)
            return JobContext(job_idx=j, jr=jr, tasks=tasks,
                          sparsity_threshold=int(perf.load_sparsity_threshold),
                              source_info=source_info,
                              sink_tables=sink_tables, fps=fps,
                              custom_sinks=custom_sinks,
                              skipped=not sink_tables and not custom_sinks)
        if table_sinks and not custom_sinks \
                and cache_mode == CacheMode.Ignore and all(
                self.db.table_is_committed(nm) for nm in sink_names):
            return JobContext(job_idx=j, jr=jr, tasks=tasks,
                          sparsity_threshold=int(perf.load_sparsity_threshold),
                              source_info=source_info, sink_tables={},
                              fps=fps, skipped=True)
        sink_tables: Dict[int, Tuple] = {}
        for sink, name in zip(table_sinks, sink_names):
            src_col = sink.input_columns()[0]
            codec = self._codec_for(src_col)
            if self.db.has_table(name):
                if self.db.table_is_committed(name) \
                        and cache_mode == CacheMode.Error:
                    raise JobException(
                        f"output stream {name} already exists "
                        f"(pass cache_mode=CacheMode.Overwrite or Ignore)")
                self.db.delete_table(name)
            is_frame = codec == "frame"
            col = md.ColumnDescriptor(
                "frame" if is_frame else "output",
                md.ColumnType.VIDEO if is_frame else md.ColumnType.BYTES,
                codec="video" if is_frame else codec)
            desc = self.db.create_table(
                name, [col], end_rows=[e for _, e in tasks], job_id=-1)
            enc = dict(sink.extra.get("encode_options") or {})
            sink_tables[sink.id] = (desc, col.name, codec, enc)
        ctx = JobContext(job_idx=j, jr=jr, tasks=tasks,
                          sparsity_threshold=int(perf.load_sparsity_threshold),
                         source_info=source_info, sink_tables=sink_tables,
                         fps=fps, custom_sinks=custom_sinks,
                         skipped=not sink_tables and not custom_sinks)
        return ctx

    @staticmethod
    def _codec_for(col: O.OpColumn) -> str:
        node = col.op
        if node.is_builtin:
            return "frame" if col.is_frame else "pickle"
        idx = [c for c, _ in node.spec.output_columns].index(col.column)
        return node.spec.output_codecs[idx]

    # ------------------------------------------------------------------
    # Execution
    # ------------------------------------------------------------------

    def setup_chains(self, info: A.GraphInfo, jobs: List[JobContext],
                     perf: PerfParams) -> None:
        """Arm stateful task affinity (one planning chain per multi-task
        job) when the graph has unbounded-state ops and the caller opted
        in.  NOTE: the whole run then executes with ONE loader and ONE
        pipeline instance on this node (kernel state lives in a single
        instance's kernels, and reordering would carry-miss) — an
        explicit trade the opt-in knob documents; cross-job parallelism
        in a cluster comes from per-job worker stickiness."""
        self._chains = {}
        if not getattr(perf, "stateful_task_affinity", False):
            return
        unbounded = [n.name for n in info.ops
                     if n.spec is not None
                     and getattr(n.spec, "unbounded_state", False)]
        if not unbounded:
            return
        for job in jobs:
            if not job.skipped and len(job.tasks) > 1:
                self._chains[job.job_idx] = _StatefulChain()
        if self._chains:
            _log.info(
                "stateful task affinity armed for %d job(s) (ops: %s): "
                "incremental plans, single evaluation instance",
                len(self._chains), ", ".join(sorted(set(unbounded))))

    # -- tracing glue (util/tracing.py) --------------------------------

    def _task_trace_begin(self, w: TaskItem, **attrs) -> None:
        """Open the task's span (idempotent): child of its trace context
        — the job root span locally, the master's assign span in
        cluster mode.  No context = no span (tracing off or untraced
        caller); every stage then runs trace-free at one None check."""
        if w.trace_span is None and w.trace_ctx is not None:
            w.trace_span = _tr.open_span(
                self.tracer, "task", parent=w.trace_ctx,
                job=w.job.job_idx, task=w.task_idx, attempt=w.attempt,
                **attrs)

    def _task_scope(self, w: TaskItem):
        """Resume the task span on the calling stage thread, so the
        stage/op profiler spans inside nest under it."""
        return _tr.use_span(self.tracer, w.trace_span)

    def _note_wait(self, name: str, counter, t0: float, **args) -> None:
        """A stage thread's wait that began at `t0` is over: its seconds
        go to `counter`, and one interval covers it whole — however many
        queue time-outs it spanned — unless it was too short to tell."""
        waited = time.time() - t0
        counter.inc(waited)
        if waited > _WAIT_SPAN_MIN_S:
            self.profiler.add_interval(name, t0, t0 + waited, level=1,
                                       **args)

    def _task_trace_end(self, w: TaskItem,
                        status: Optional[str] = None) -> None:
        span, w.trace_span = w.trace_span, None
        _tr.close_span(self.tracer, span, status=status)
        if w.enqueued_at:
            # enqueue -> sink-committed (or terminal failure; errors are
            # latency too in a serving SLO)
            _M_TASK_LATENCY.observe(time.time() - w.enqueued_at)

    def run(self, outputs: Sequence[O.OpNode], perf: PerfParams,
            cache_mode: CacheMode = CacheMode.Error,
            show_progress: bool = False) -> List[JobContext]:
        prof = self.profiler
        prof.level = int(getattr(perf, "profiler_level", 1))
        _M_RUNS.inc()
        with prof.span("run:prepare", level=0,
                       counter=_M_RUN_SECONDS.labels(phase="prepare")):
            info, jobs = self.prepare(outputs, perf, cache_mode)
            self.setup_chains(info, jobs, perf)
            self._stream_opt = bool(
                getattr(perf, "stream_work_packets", True))
            work = [TaskItem(job, t, rng)
                    for job in jobs if not job.skipped
                    for t, rng in enumerate(job.tasks)]
        _log.info("job set prepared: %d jobs (%d skipped), %d tasks",
                  len(jobs), sum(1 for j in jobs if j.skipped), len(work))
        # the job's root trace span: every task span of this run chains
        # up to it under one trace_id (Client.trace assembles the tree)
        root = _tr.open_span(self.tracer, "job",
                             tasks=len(work), jobs=len(jobs))
        self.last_trace_id = root.trace_id if root is not None else None
        now = time.time()
        for w in work:
            if root is not None:
                w.trace_ctx = root.context()
            w.enqueued_at = now
        try:
            if work:
                # level >= 2: capture the XLA device timeline around the
                # job (SURVEY §5; merged into Profile.write_trace output)
                from ..util.jaxprof import device_trace
                with self._save_end_lock:
                    self._last_save_end = None
                self.stage_widths = None
                with device_trace(prof), prof.span(
                        "run:pipeline", level=0, tasks=len(work),
                        counter=_M_RUN_SECONDS.labels(phase="pipeline")
                        ) as pipeline:
                    try:
                        self._run_pipeline(
                            info, work, show_progress,
                            queue_size=int(perf.queue_size_per_pipeline),
                            precompile=self.precompile_hint(jobs))
                    finally:
                        # what the run paid after its last task was
                        # committed: the stage threads' joins and the
                        # evaluators' close()
                        joined = time.time()
                        if self.stage_widths is not None:
                            loaders, instances, savers = self.stage_widths
                            pipeline.args.update(loaders=loaders,
                                                 instances=instances,
                                                 savers=savers)
                            _M_RUN_LOADERS.inc(loaders)
                        if self._last_save_end is not None:
                            _M_RUN_SECONDS.labels(phase="drain").inc(
                                joined - self._last_save_end)
                            prof.add_interval("run:drain",
                                              self._last_save_end, joined,
                                              level=0)
        finally:
            _tr.close_span(self.tracer, root)
        with prof.span("run:commit", level=0,
                       counter=_M_RUN_SECONDS.labels(phase="commit")):
            ran = [job for job in jobs if not job.skipped]
            tables = [desc.id for job in ran
                      for desc, _c, _k, _e in job.sink_tables.values()]
            with prof.span(
                    "commit:tables", tables=len(tables),
                    counter=_M_RUN_PART_SECONDS.labels(part="tables")):
                for table_id in tables:
                    self.db.commit_table(table_id)
            with prof.span(
                    "commit:sinks",
                    counter=_M_RUN_PART_SECONDS.labels(part="sinks")):
                for job in ran:
                    for stream in job.custom_sinks.values():
                        # durability barrier (reference Sink::finished)
                        stream.storage.finished(stream,
                                                job.jr.output_rows)
            with prof.span(
                    "commit:megafile",
                    counter=_M_RUN_PART_SECONDS.labels(part="megafile")
                    ) as span:
                packed, nbytes = self.db.write_megafile()
                span.args = {"tables": packed, "bytes": nbytes}
            _M_MEGAFILE_BYTES.inc(nbytes)
        return jobs

    @staticmethod
    def precompile_hint(jobs: List[JobContext]
                        ) -> Optional[Tuple[int, int, int]]:
        """(frame_h, frame_w, work_packet_size) for the evaluator's
        bucket-ladder warm-up (evaluate.py precompile), from the first
        non-skipped job with a video source — the geometry the device
        kernels will actually see.  None = nothing to warm."""
        for job in jobs:
            if getattr(job, "skipped", False):
                continue
            for si in job.source_info.values():
                vm = si.get("video_meta")
                if vm is not None and vm.height and vm.width:
                    wp = int(getattr(job.jr, "work_packet_size", 0) or 0)
                    return (int(vm.height), int(vm.width), wp)
        return None

    def _run_pipeline(self, info: A.GraphInfo, work: List[TaskItem],
                      show_progress: bool,
                      queue_size: Optional[int] = None,
                      precompile: Optional[Tuple[int, int, int]] = None
                      ) -> None:
        pending = list(work)
        src_lock = threading.Lock()

        def source():
            with src_lock:
                return pending.pop(0) if pending else None

        done = self.run_pipeline(info, source, show_progress=show_progress,
                                 total=len(work), queue_size=queue_size,
                                 precompile=precompile)
        if done != len(work):
            raise JobException(
                f"pipeline finished {done}/{len(work)} tasks")

    def run_pipeline(self, info: A.GraphInfo, source,
                     on_start=None, on_done=None, on_eval_done=None,
                     on_task_error=None,
                     queue_size: Optional[int] = None,
                     show_progress: bool = False, total: int = 0,
                     precompile: Optional[Tuple[int, int, int]] = None
                     ) -> int:
        """Multi-stage streaming pipeline (reference worker.cpp:1467-1724
        load/evaluate/save stage drivers): N loaders pull TaskItems from
        `source` and decode, P evaluator instances execute, S savers
        persist.  Shared by the local executor (source = task list) and the
        cluster worker (source = master NextWork pull), so a cluster worker
        keeps every stage of the node busy instead of running one task at a
        time.

        source() -> TaskItem | "wait" (retry shortly) | None (exhausted);
        called concurrently from loader threads.
        on_start(w) -> bool | None: evaluation-begin hook (cluster:
        StartedWork RPC); returning False drops the task without
        evaluating (revoked attempt).  on_eval_done(w): evaluation-complete
        hook, fired when the task hands off to the save stage (cluster:
        EvalDone RPC so save-parked tasks stop counting against the
        NextWork window).  on_done(w): save-complete hook (cluster:
        FinishedWork RPC).
        on_task_error(w, exc) -> bool: True = task failure is reported and
        the pipeline continues (cluster); False/None = abort (local).
        Evaluators are kept across pipeline entries by `self.evaluators`
        (a Client's, a cluster worker's), if there is one.
        Returns the number of tasks fully saved.

        SCANNER_TPU_NO_PIPELINING=1 (reference worker.cpp:140 NO_PIPELINING)
        degrades to a single-threaded sequential loop — same semantics,
        clean stack traces for debugging."""
        import os
        # the health/SLO engine watches this pipeline's queue-depth and
        # stage-rate series: make sure it samples while stages run,
        # even when no Client/Worker constructor started it (direct
        # LocalExecutor embedding, spawned test workers)
        from ..util import health as _health
        _health.ensure_started()
        # and the remediation controller rides the same alerts: local
        # runs get the worker-local playbooks (frame-cache shrink,
        # ladder re-warm) with no cluster in sight
        from . import controller as _controller
        _controller.ensure_started()
        if os.environ.get("SCANNER_TPU_NO_PIPELINING", "0") not in \
                ("0", "", "false"):
            self.stage_widths = (1, 1, 1)  # this thread is every stage
            return self._run_serial(info, source, on_start, on_done,
                                    on_eval_done, on_task_error,
                                    show_progress, total, precompile)
        qsize = queue_size or 4
        # stateful affinity: kernel state lives in ONE instance's kernels,
        # so a chained run serializes evaluation (the reference pins a
        # job's packets to one worker for the same reason).  One loader
        # too: with N loaders, a decode-time inversion hands the
        # evaluator task t+1 before t and every inversion costs a
        # StateCarryMiss reload+recompute — per-task decode parallelism
        # stays available via decoder_threads.
        # An unbounded-state op serializes the same way, chained or
        # not, and so does a bounded-state one with a warm-up of 0 (its
        # tasks continue one another): their kernels are reset only on
        # a row DISCONTINUITY, so order is correctness there, not a
        # perf knob.  A bounded-state task with a warm-up begins with
        # the rows that make its state and is reset at its first
        # compute row (evaluate.py _run_kernel): it stands alone, and
        # the run keeps its loaders and instances.
        ordered = any(not n.stands_alone() for n in info.ops)
        serialize = bool(self._chains) or ordered
        n_evals = 1 if serialize else self.pipeline_instances
        # Device-affine routing: when instances own distinct chips, each
        # gets its OWN queue and the loader assigns each task to the
        # least-loaded instance (round-robin tie-break) at enqueue time
        # — the assignment is recorded on the TaskItem before loading so
        # device staging targets the chip that will evaluate the task.
        # A chained run (n_evals=1) or a single-chip host keeps today's
        # shared queue (any instance takes any task).
        from .evaluate import (assigned_device, default_load_workers,
                               device_label)
        inst_devices = [assigned_device(i) for i in range(n_evals)]
        if n_evals > 1 and any(d is not None for d in inst_devices):
            eval_qs = [_StageQueue(qsize, _loaded_whole)
                       for _ in range(n_evals)]
        else:
            eval_qs = [_StageQueue(qsize, _loaded_whole)] * n_evals
        uniq_qs = list({id(q): q for q in eval_qs}.values())
        save_q = _StageQueue(qsize)
        n_loaders = 1 if serialize else default_load_workers(
            self.num_load_workers, instances=n_evals, queues=len(uniq_qs),
            qsize=qsize, tasks=total, decoder_threads=self.decoder_threads,
            streaming=self._stream_packets())
        self.stage_widths = (n_loaders, n_evals, self.num_save_workers)
        _M_LOAD_WORKERS.set(n_loaders)
        # live depth gauges sample the queues at scrape time; the last
        # pipeline to start owns the gauge (concurrent pipelines in one
        # process share the process registry)
        depth_fns = {
            "evaluate": lambda: sum(q.count(_awaits_evaluator)
                                    for q in uniq_qs),
            "save": save_q.qsize,
        }
        for stage, fn in depth_fns.items():
            _M_QDEPTH.labels(stage=stage).set_function(fn)
        errors: List[BaseException] = []
        err_lock = threading.Lock()
        # errors only: a run that ends well never sets it
        stop = threading.Event()

        def record_err(e: BaseException):
            with err_lock:
                errors.append(e)
            stop.set()
            # wake whoever is blocked on a hand-off whose other side
            # may be dead
            for q in uniq_qs + [save_q]:
                q.abort()

        def task_failed(w: TaskItem, e: BaseException) -> None:
            """Route one task's failure; abort unless the error handler
            accepts it (cluster mode reports FailedWork and moves on)."""
            self._fail_task(w, e)
            if on_task_error is not None and on_task_error(w, e):
                return
            _log.exception("task (%d,%d) failed; aborting pipeline",
                           w.job.job_idx, w.task_idx, exc_info=e)
            record_err(e)

        # loader cache: (thread, job, node) -> DecoderAutomata
        tls = threading.local()

        # Enqueue-time instance assignment: fixes which evaluator — and
        # therefore which chip — a task runs on, BEFORE the loader
        # stages its columns.  Least-loaded queue wins so one slow task
        # can't head-of-line-block the whole pipeline (strict
        # round-robin would keep feeding the slow instance until its
        # queue filled and the loader stalled while other chips
        # drained); a rotating start index breaks qsize ties fairly, so
        # an idle pipeline still spreads tasks across every chip.
        assign_lock = threading.Lock()
        assign_counter = [0]

        def assign_instance(w: TaskItem) -> None:
            with assign_lock:
                start = assign_counter[0] % n_evals
                assign_counter[0] += 1
            best = min(
                range(n_evals),
                key=lambda k: (eval_qs[(start + k) % n_evals].qsize(), k))
            idx = (start + best) % n_evals
            w.instance = idx
            w.device = inst_devices[idx]

        def next_item(q: _StageQueue, wait_name: str, wait_counter,
                      **args):
            """The stage's next item, or None once the pipeline stops or
            the stage before has finished and left `q` empty.  One wait,
            one interval."""
            t0 = time.time()
            item = q.get()
            self._note_wait(wait_name, wait_counter, t0, **args)
            return item

        def loader():
            try:
                try:
                    while not stop.is_set():
                        w = source()
                        if w is None:
                            break
                        if w == "wait":
                            time.sleep(0.2)
                            continue
                        assign_instance(w)
                        self._task_trace_begin(w)
                        try:
                            with self._task_scope(w):
                                self.load_task(info, w, tls)
                        except Exception as e:  # noqa: BLE001
                            task_failed(w, e)
                            continue
                        t_put = time.time()
                        placed = eval_qs[w.instance].put(w)
                        self._note_wait("load:queue_wait", _M_WAIT_LOAD, t_put,
                                        task=w.task_idx, job=w.job.job_idx)
                        if placed and w.chunk_plans is not None:
                            # streaming task: decode chunks into its
                            # bounded queue while the evaluator consumes
                            with self._task_scope(w):
                                self._produce_chunks(info, w, tls,
                                                     stop=stop)
                finally:
                    # release decoder handles held by this loader thread
                    for auto in getattr(tls, "automata", {}).values():
                        auto.close()
                    if hasattr(tls, "automata"):
                        tls.automata = {}
            except BaseException as e:  # noqa: BLE001
                record_err(e)

        def evaluator(evaluator_idx: int):
            te = None
            my_q = eval_qs[evaluator_idx]
            dev_lbl = device_label(inst_devices[evaluator_idx])
            import types
            fb_tls = types.SimpleNamespace()  # fallback reload decoders
            try:
                # fetch_resources runs once per node: instance 0 fetches,
                # the rest only setup (reference evaluate_worker.cpp:488-534)
                if evaluator_idx > 0:
                    fetch_done.wait()
                te = self._open_evaluator(
                    info, evaluator_idx, n_evals,
                    skip_fetch=evaluator_idx > 0, precompile=precompile)
                if evaluator_idx == 0:
                    fetch_done.set()
                while True:
                    w = next_item(my_q, "evaluate:task_wait", _M_WAIT_EVAL,
                                  device=dev_lbl)
                    if w is None:
                        break
                    try:
                        chunks = None if w.chunk_q is None \
                            else self._queued_chunks(w, stop)
                        if not self._evaluate_stage(info, te, w, fb_tls,
                                                    chunks, on_start):
                            continue  # revoked attempt: drop silently
                    except Exception as e:  # noqa: BLE001
                        task_failed(w, e)
                        continue
                    if on_eval_done is not None:
                        on_eval_done(w)
                    t_put = time.time()
                    placed = save_q.put(w)
                    self._note_wait("evaluate:save_wait", _M_WAIT_EVAL_OUT,
                                    t_put, task=w.task_idx, device=dev_lbl)
                    if not placed:
                        break
            except BaseException as e:  # noqa: BLE001
                record_err(e)
            finally:
                fetch_done.set()  # never leave siblings waiting
                self._close_evaluator(te, fb_tls)

        done_count = [0]
        done_lock = threading.Lock()

        def saver():
            try:
                while True:
                    w = next_item(save_q, "save:queue_wait", _M_WAIT_SAVE)
                    if w is None:
                        break
                    try:
                        self.save_results(info, w, on_done)
                    except Exception as e:  # noqa: BLE001
                        task_failed(w, e)
                        continue
                    with done_lock:
                        done_count[0] += 1
                        if show_progress:
                            print(f"\rtasks {done_count[0]}/{total}",
                                  end="", flush=True)
            except BaseException as e:  # noqa: BLE001
                record_err(e)

        fetch_done = threading.Event()

        loaders = [threading.Thread(target=loader, name=f"load-{i}")
                   for i in range(n_loaders)]
        evals = [threading.Thread(target=evaluator, args=(i,),
                                  name=f"eval-{i}")
                 for i in range(n_evals)]
        savers = [threading.Thread(target=saver, name=f"save-{i}")
                  for i in range(self.num_save_workers)]
        try:
            # evaluators first: taking over a kept evaluator needs 0.1 ms
            # of the interpreter lock, which it gets at once while no
            # loader is running yet
            for t in evals + loaders + savers:
                t.start()
            # each hand-off closes when the stage that fills it is done
            for t in loaders:
                t.join()
            for q in uniq_qs:
                q.close()
            for t in evals:
                t.join()
            save_q.close()
            for t in savers:
                t.join()
        finally:
            # detach the depth gauges from this run's (now dead) queues —
            # but only if this run still owns them: a concurrent pipeline
            # that re-bound the gauge keeps its live sampler
            for stage, fn in depth_fns.items():
                g = _M_QDEPTH.labels(stage=stage)
                if g.clear_function(fn):
                    g.set(0)
        if show_progress:
            print()
        if errors:
            raise errors[0]
        return done_count[0]

    def _run_serial(self, info: A.GraphInfo, source, on_start, on_done,
                    on_eval_done, on_task_error, show_progress: bool,
                    total: int,
                    precompile: Optional[Tuple[int, int, int]] = None
                    ) -> int:
        """The NO_PIPELINING path: every stage inline on this thread.
        A task failure is routed as the threaded path routes it (load /
        evaluate(+on_start) / save(+on_done) failures are the task's and
        on_task_error may absorb them; an on_eval_done failure is the
        pipeline's), but what is not absorbed raises from here."""
        import types
        tls = types.SimpleNamespace()
        fb_tls = types.SimpleNamespace()  # carry-miss fallback decoders
        te = None
        done = 0

        def absorbed(w: TaskItem, e: BaseException) -> bool:
            self._fail_task(w, e)
            return on_task_error is not None and on_task_error(w, e)

        try:
            te = self._open_evaluator(info, precompile=precompile)
            while True:
                w = source()
                if w is None:
                    break
                if w == "wait":
                    time.sleep(0.2)
                    continue
                # single inline instance: staging still targets its
                # assigned chip so serial runs match the threaded path
                w.device = te.device
                self._task_trace_begin(w)
                try:
                    with self._task_scope(w):
                        self.load_task(info, w, tls)
                    # a streaming task decodes inline on this one thread;
                    # the carry-miss fallback loads through fb_tls — NOT
                    # tls, whose decoder sessions are suspended mid-run
                    # and must not be reset
                    chunks = None if w.chunk_plans is None \
                        else self._iter_chunk_items(info, w, tls)
                    if not self._evaluate_stage(info, te, w, fb_tls,
                                                chunks, on_start):
                        continue  # revoked attempt
                except Exception as e:  # noqa: BLE001
                    if absorbed(w, e):
                        continue
                    raise
                if on_eval_done is not None:
                    on_eval_done(w)
                try:
                    self.save_results(info, w, on_done)
                except Exception as e:  # noqa: BLE001
                    if absorbed(w, e):
                        continue
                    raise
                done += 1
                if show_progress:
                    print(f"\rtasks {done}/{total}", end="", flush=True)
        finally:
            self._close_evaluator(te, tls, fb_tls)
        if show_progress:
            print()
        return done

    def run_single_task(self, info: A.GraphInfo, w: TaskItem,
                        save: bool = True,
                        span_attrs: Optional[Dict[str, Any]] = None
                        ) -> TaskItem:
        """Run ONE task stage-inline on this thread: load → evaluate
        (→ save).  The gang-member path (engine/gang.py): a gang task
        executes inside a dedicated member process, synchronized with
        its peers by collectives rather than by the streaming pipeline,
        and only member 0 saves — so the member defers `save` until the
        cross-host agreement check passes (`save_results` finishes the
        job).  `span_attrs` land on the task span (gang id / epoch /
        member rank, so per-host stragglers stay attributable under the
        gang root span).  Returns `w` with `.results` populated."""
        import types
        tls = types.SimpleNamespace()
        fb_tls = types.SimpleNamespace()
        self._task_trace_begin(w, **(span_attrs or {}))
        te = None
        try:
            with self._task_scope(w):
                self.load_task(info, w, tls)
            te = self._open_evaluator(info)
            w.device = te.device
            self._evaluate_stage(info, te, w, fb_tls)
            if save:
                self.save_results(info, w)
            return w
        except Exception as e:  # noqa: BLE001
            self._fail_task(w, e)
            raise
        finally:
            self._close_evaluator(te, tls, fb_tls)

    # ------------------------------------------------------------------
    # The stage bodies of one task.  Every driver of a task runs these:
    # the stage threads of run_pipeline, the one thread of _run_serial,
    # the gang member (run_single_task).  The drivers differ in how a
    # task reaches a stage and in where its failure goes, not in what
    # the stage does and records.
    # ------------------------------------------------------------------

    def _open_evaluator(self, info: A.GraphInfo, idx: int = 0,
                        instances: int = 1, skip_fetch: bool = False,
                        precompile: Optional[Tuple[int, int, int]] = None
                        ) -> TaskEvaluator:
        """Pipeline instance `idx`'s evaluator under the
        `evaluate:setup` span: the one `self.evaluators` kept from the
        last run of this graph, or a new one."""
        from .evaluate import assigned_device, device_label
        with self.profiler.span(
                "evaluate:setup", level=0, counter=_M_EVAL_SETUP_SECONDS,
                device=device_label(assigned_device(idx))) as span:
            key, te = None, None
            if self.evaluators is not None:
                key, te = self.evaluators.take(info, self.profiler, idx,
                                               instances, precompile)
            span.args["reused"] = te is not None
            if te is not None:
                _M_EVAL_REUSES.inc()
            else:
                te = TaskEvaluator(
                    info, self.profiler, skip_fetch_resources=skip_fetch,
                    precompile=precompile, instance=idx,
                    instances=instances,
                    yuv_wire=any(self._yuv_device_wire(info, n.id)
                                 for n in info.ops
                                 if n.name == O.INPUT_OP))
                te.pool_key = key
        _M_EVAL_SETUPS.inc()
        return te

    def _close_evaluator(self, te: Optional[TaskEvaluator],
                         *decoders) -> None:
        """The end of an evaluator's driver: the decoder handles its
        thread held (`decoders`: the namespaces they were cached in) go,
        and the evaluator goes back to whoever keeps it, or is closed."""
        for ns in decoders:
            for auto in getattr(ns, "automata", {}).values():
                auto.close()
        if te is None:
            return
        if self.evaluators is not None:
            self.evaluators.give(te)
        else:
            te.close()

    def _evaluate_stage(self, info: A.GraphInfo, te: TaskEvaluator,
                        w: TaskItem, fb_tls, chunks=None,
                        on_start=None) -> bool:
        """The evaluate stage of one loaded task, on the calling thread.
        `chunks` is how a streaming task's (plan, elements) arrive —
        `_queued_chunks` from its loader, `_iter_chunk_items` decoded
        inline — and None for a task loaded whole.  False: `on_start`
        revoked the attempt and nothing ran.  What evaluation raises is
        the caller's to route (`_fail_task` is the cleanup)."""
        from .evaluate import device_label
        if on_start is not None and on_start(w) is False:
            if w.chunk_abort is not None:
                w.chunk_abort.set()  # unblock the loader
            # leases the producing loader adds after this are released
            # by its abort path
            self._release_cache(w)
            self._task_trace_end(w, status="revoked")
            return False
        t0 = time.time()
        lbl = device_label(w.device)
        # open seconds accrue while the task runs: a long task never
        # lands in one health sample
        with _M_EVAL_OPEN.labels(device=lbl).timing(), \
                self._task_scope(w), \
                self.profiler.span("evaluate", level=0, task=w.task_idx,
                                   job=w.job.job_idx, device=lbl):
            if chunks is not None:
                try:
                    w.results = self._consume_iter(info, te, w, chunks,
                                                   fb_tls)
                except BaseException:
                    w.chunk_abort.set()  # any failure stops the producer
                    raise
            else:
                w.results = self._evaluate_with_fallback(info, te, w,
                                                         fb_tls)
            # start the sink d2h now: the copy rides under the NEXT
            # task's evaluation instead of blocking the saver
            with self.profiler.span(
                    "evaluate:prefetch",
                    counter=_M_EVAL_PART_SECONDS.labels(part="prefetch"),
                    task=w.task_idx) as span:
                span.args.update(self._prefetch_results(w))
        dt = time.time() - t0
        _M_STAGE_SECONDS.labels(stage="evaluate").inc(dt)
        _M_STAGE_TASKS.labels(stage="evaluate").inc()
        _M_DEV_TASKS.labels(device=lbl).inc()
        w.elements = None
        # evaluation is done with the cached pages: unpin them (the sink
        # batches are the task's own arrays, never cache pages)
        self._release_cache(w)
        return True

    def save_results(self, info: A.GraphInfo, w: TaskItem,
                     on_done=None) -> None:
        """The save stage of one evaluated task: persist its results and
        close its span.  Also the deferred half of
        `run_single_task(save=False)`, run by a gang's single writer
        (member 0) only after the collective agreement check passed."""
        t0 = time.time()
        with self._task_scope(w):
            with self.profiler.span("save", level=0, task=w.task_idx,
                                    job=w.job.job_idx):
                self._save_task(info, w)
        # saved: its results go now, as a failed task's do.  The run's
        # work list holds every TaskItem until the run returns, and a
        # frame column's results are 6.2 MB of HBM a 1080p row
        w.results = None
        t_saved = time.time()
        _M_STAGE_SECONDS.labels(stage="save").inc(t_saved - t0)
        _M_STAGE_TASKS.labels(stage="save").inc()
        with self._save_end_lock:
            # savers finish in any order: keep the latest
            self._last_save_end = max(self._last_save_end or 0.0, t_saved)
        # close the span BEFORE on_done: the cluster worker's completion
        # hook ships spans then sends FinishedWork, so the master holds
        # this task's full chain before the bulk can finish
        self._task_trace_end(w)
        if on_done is not None:
            on_done(w)

    def _fail_task(self, w: TaskItem, e: BaseException) -> None:
        """What a failed attempt gets from whoever drove it: the error
        on its span, the span closed, and nothing of it left held.  Its
        staged columns and results go NOW: a task requeued after memory
        pressure must not keep holding the very device buffers that
        caused it (the ledger releases as the arrays are collected),
        and cache pins must not outlive the attempt."""
        if w.trace_span is not None:
            w.trace_span.add_event("error", type=type(e).__name__,
                                   message=str(e)[:200])
        self._task_trace_end(w, status="error")
        w.elements = None
        w.results = None
        self._release_cache(w)

    # ------------------------------------------------------------------
    # Work-packet streaming (PerfParams.stream_work_packets)
    # ------------------------------------------------------------------

    class _VideoFeed:
        """Incremental frame supply for one video source node of one
        streaming task: one decode session over the task's item
        (video.automata.StreamSession) that writes each chunk's frames
        into the array the chunk is staged from, and retention of the
        rows later chunks reach back to, driven by their minimum row, so
        stencil back-reach is served from memory instead of a per-chunk
        keyframe re-decode (the reference's element cache,
        evaluate_worker.h:207-218)."""

        def __init__(self, ex: "LocalExecutor", w: TaskItem, tls,
                     node_id: int, si, plans: List[A.TaskPlan],
                     output_format: str, use_cache: bool = False):
            desc = si["table"]
            all_rows = np.unique(np.concatenate([
                np.asarray(p.source_rows[node_id], np.int64)
                for p in plans]))
            # suffix minima: after serving chunk i, rows below the
            # smallest row any LATER chunk requests can be dropped
            mins = [int(np.asarray(p.source_rows[node_id]).min())
                    if len(p.source_rows[node_id]) else np.iinfo(np.int64).max
                    for p in plans]
            suffix = []
            cur = np.iinfo(np.int64).max
            for m in reversed(mins):
                suffix.append(cur)
                cur = min(cur, m)
            self._keep_from = list(reversed(suffix))  # per chunk index
            self._chunk_i = 0
            self._profiler = ex.profiler
            # row -> frame of the rows a later chunk still needs: its
            # row of the chunk array it was decoded into (dropped as
            # _keep_from says, the array with it), or memory of its
            # own for a row that was not decoded into its slot
            self._held: Dict[int, np.ndarray] = {}

            # feed the codec packets in slices matched to the chunk row
            # count: a call fills about one work packet
            wp_est = max(4, max(len(p.source_rows[node_id])
                                for p in plans))

            # the streamable guard (load_task) pins the task to ONE
            # item; its own descriptor drives the convert-mark geometry
            # (items of one table may differ — same rule as the
            # whole-task loader's per-item marks)
            item = desc.item_of_row(int(all_rows[0]))
            item_start, item_end = desc.item_bounds(item)
            self._item_start = int(item_start)
            auto = ex._automata(tls, w.job, node_id, si, item,
                                output_format=output_format)
            self._auto = auto
            self.convert = (("yuv420", auto.vd.height, auto.vd.width)
                            if output_format == "yuv420" else None)
            self._hw = (auto.vd.height, auto.vd.width)

            # frame cache (engine/framecache.py): one plan for the
            # whole task's rows, pinned up front — the decode stream
            # then covers only the misses, and each chunk assembles as
            # a page gather + a staging copy of its fresh rows
            self._plan = None
            self._cache = None
            decode_rows = all_rows
            if use_cache:
                cache = _fc.cache()
                plan = cache.plan(
                    w.device, (ex._cache_db_key, desc.id), si["column"],
                    item, output_format, all_rows - item_start,
                    total_rows=item_end - item_start,
                    keyint=ex._keyint_of(si))
                _fc.attach_lease(w, plan.lease)
                self._plan = plan
                self._cache = cache
                self._miss = set((plan.miss_rows
                                  + item_start).tolist())
                decode_rows = plan.miss_rows + item_start
            from ..video.automata import StreamSession
            self._session = StreamSession(
                auto, (decode_rows - item_start).tolist(),
                packets_per_call=wp_est)

        def _decode_into(self, out: np.ndarray) -> List[int]:
            """The session's next len(out) rows decoded into `out`;
            returns them as table rows, out[i] holding the i-th."""
            lbl = threading.current_thread().name
            codec0 = self._auto.codec_frames
            with self._profiler.span(
                    "load:decode", frames=len(out),
                    counter=_M_DECODE_SECONDS.labels(loader=lbl)):
                got = self._session.decode_into(out)
            _M_DECODED.labels(loader=lbl).inc(len(got))
            _M_CODEC_FRAMES.labels(loader=lbl).inc(
                self._auto.codec_frames - codec0)
            return (got + self._item_start).tolist()

        def _fill(self, fresh: List[int]) -> np.ndarray:
            """The chunk's host assembly: its array, slot i row
            fresh[i]'s (strictly ascending, as a ColumnBatch's rows
            are).  A row held from an earlier chunk is copied in, the
            others are decoded where they lie, in runs of consecutive
            slots; each is counted by how it came there."""
            with self._profiler.span("load:assemble",
                                     counter=_M_LOAD_ASSEMBLE) as span:
                data = np.empty((len(fresh),) + self._auto.frame_shape,
                                np.uint8)
                # the interval keeps this dict, so its counts read as
                # they stand when the chunk is whole
                span.args = how = dict(rows=len(fresh), bytes=data.nbytes,
                                       direct=0, carried=0, moved=0)
                lack: Dict[int, int] = {}  # row -> slot, ascending
                for i, r in enumerate(fresh):
                    held = self._held.get(r)
                    if held is None:
                        lack[r] = i
                    else:
                        data[i] = held
                        how["carried"] += 1
            while lack:
                i = next(iter(lack.values()))
                n = 1
                while i + n < len(fresh) and fresh[i + n] in lack:
                    n += 1
                got = self._decode_into(data[i:i + n])
                m = 0
                while m < len(got) and got[m] == fresh[i + m]:
                    del lack[got[m]]
                    m += 1
                how["direct"] += m
                if m == len(got):
                    continue
                # from here on other rows than the slots', or in another
                # order (an open-GOP head that the retry delivers late;
                # a chunk that skips rows a later one wants): out of
                # the slots, each to its own or to wait for its chunk
                with self._profiler.span("load:assemble",
                                         rows=len(got) - m,
                                         counter=_M_LOAD_ASSEMBLE):
                    for r, f in zip(got[m:],
                                    data[i + m:i + len(got)].copy()):
                        if r in lack:
                            data[lack.pop(r)] = f
                            how["moved"] += 1
                        else:
                            self._held[r] = f
            for h in ("direct", "carried", "moved"):
                if how[h]:
                    _M_LOAD_ASSEMBLED.labels(how=h).inc(how[h])
            return data

        def batch_for(self, rows: Sequence[int]) -> ColumnBatch:
            rows_arr = np.asarray(rows, np.int64)
            if self._plan is None:
                fresh = rows_arr.tolist()
            else:
                # page-gather assembly: fresh (miss) rows of this chunk
                # feed page completion and stage once; resident rows
                # gather from the pinned pages on this task's chip
                fresh = sorted(set(rows_arr.tolist()) & self._miss)
            data = self._fill(fresh)
            keep_from = self._keep_from[self._chunk_i]
            self._chunk_i += 1
            for r in [r for r in self._held if r < keep_from]:
                del self._held[r]
            for i in range(bisect.bisect_left(fresh, keep_from), len(fresh)):
                self._held[fresh[i]] = data[i]
            if self._plan is not None:
                fresh_local = np.asarray(fresh, np.int64) \
                    - self._item_start
                with self._profiler.span(
                        "load:stage", counter=_M_LOAD_STAGE,
                        rows=len(rows_arr), fresh=len(fresh)):
                    data = self._cache.assemble_rows(
                        self._plan, rows_arr - self._item_start,
                        fresh_local, data, hw=self._hw)
            return ColumnBatch(rows_arr, data, convert=self.convert)

    def _iter_chunk_items(self, info: A.GraphInfo, w: TaskItem, tls):
        """Yield (plan, elements) per work-packet chunk of a streaming
        task, decoding incrementally and pre-staging device columns so
        the h2d of chunk k+1 rides under the compute of chunk k."""
        feeds: Dict[int, LocalExecutor._VideoFeed] = {}
        t0 = time.time()
        with self.profiler.span("load", level=0, task=w.task_idx,
                                job=w.job.job_idx), \
                self.profiler.span("load:open", counter=_M_LOAD_OPEN):
            for nid in w.chunk_plans[0].source_rows:
                si = w.job.source_info[nid]
                if si.get("is_video") and "custom" not in si:
                    fmt = ("yuv420" if self._yuv_device_wire(info, nid)
                           else "rgb24")
                    feeds[nid] = self._VideoFeed(
                        self, w, tls, nid, si, w.chunk_plans, fmt,
                        use_cache=self._cache_eligible(info, nid))
        _M_STAGE_SECONDS.labels(stage="load").inc(time.time() - t0)
        for plan in w.chunk_plans:
            elements: Dict[int, ColumnBatch] = {}
            t0 = time.time()
            with self.profiler.span("load", level=0, task=w.task_idx,
                                    job=w.job.job_idx,
                                    chunk=plan.output_range[0]):
                for nid, rows in plan.source_rows.items():
                    if nid in feeds:
                        elements[nid] = feeds[nid].batch_for(rows)
                    else:
                        elements[nid] = self._load_plain_source(
                            w, nid, [int(r) for r in rows])
                self._prestage_device_columns(info, w, elements=elements)
            _M_STAGE_SECONDS.labels(stage="load").inc(time.time() - t0)
            yield plan, elements

    def _chunk_put(self, w: TaskItem, item, stop) -> bool:
        t0 = time.time()
        try:
            while True:
                if (stop is not None and stop.is_set()) \
                        or w.chunk_abort.is_set():
                    return False
                try:
                    w.chunk_q.put(item, timeout=0.25)
                    return True
                except queue.Full:
                    pass
        finally:
            self._note_wait("load:queue_wait", _M_WAIT_LOAD, t0,
                            task=w.task_idx, job=w.job.job_idx)

    def _produce_chunks(self, info: A.GraphInfo, w: TaskItem, tls,
                        stop=None) -> None:
        """Loader-side: decode chunks into the task's bounded queue; a
        consumer failure (chunk_abort) or pipeline stop unblocks us."""
        try:
            for item in self._iter_chunk_items(info, w, tls):
                if not self._chunk_put(w, item, stop):
                    return
            self._chunk_put(w, _CHUNK_DONE, stop)
        except Exception as e:  # noqa: BLE001 — surfaces on the consumer
            self._chunk_put(w, (_CHUNK_ERR, e), stop)
        finally:
            # aborted task (consumer failure/revoke, pipeline stop):
            # unpin frame-cache pages HERE — production has ended, so
            # no later append races this release; the consumer's own
            # release paths cover the normal completion order
            if (w.chunk_abort is not None and w.chunk_abort.is_set()) \
                    or (stop is not None and stop.is_set()):
                self._release_cache(w)

    def _consume_iter(self, info: A.GraphInfo, te, w: TaskItem,
                      chunk_iter, fb_tls) -> Dict[int, ColumnBatch]:
        """Execute (plan, elements) chunks from any iterator; merge
        per-sink results in row order."""
        if _faults.ACTIVE:
            _faults.inject("pipeline.eval",
                           detail=f"task={w.job.job_idx},{w.task_idx}")
        parts: Dict[int, List[ColumnBatch]] = {}
        n = 0
        for plan, elements in chunk_iter:
            res = self._execute_chunk(info, te, w, plan, elements, fb_tls)
            for sid, b in res.items():
                parts.setdefault(sid, []).append(b)
            n += 1
        self.profiler.count("stream_chunks", n)
        with self.profiler.span(
                "evaluate:merge", chunks=n,
                counter=_M_EVAL_PART_SECONDS.labels(part="merge")):
            return {sid: concat_batches(lst)
                    for sid, lst in parts.items()}

    def _queued_chunks(self, w: TaskItem, stop=None):
        """Evaluator-side: a streaming task's chunks as they arrive over
        its producer queue."""
        while True:
            t0 = time.time()
            while True:
                try:
                    item = w.chunk_q.get(timeout=0.25)
                    break
                except queue.Empty:
                    if stop is not None and stop.is_set():
                        raise JobException(
                            "pipeline stopped during streaming task")
            # starvation attribution: time the evaluator spent waiting
            # on the loader's chunk production (decode slower than
            # compute shows up here, not as inflated kernel spans)
            self._note_wait("evaluate:chunk_wait", _M_CHUNK_WAIT, t0,
                            task=w.task_idx, job=w.job.job_idx)
            if item is _CHUNK_DONE:
                return
            if isinstance(item, tuple) and item[0] is _CHUNK_ERR:
                raise item[1]
            yield item

    def _execute_chunk(self, info: A.GraphInfo, te, w: TaskItem, plan,
                       elements, fb_tls) -> Dict[int, ColumnBatch]:
        from .evaluate import StateCarryMiss
        try:
            return te.execute_task(w.job.jr, plan, elements)
        except StateCarryMiss as e:
            _log.info("task (%d,%d) chunk %s: %s — re-running "
                      "self-contained", w.job.job_idx, w.task_idx,
                      plan.output_range, e)
            self.profiler.count("state_carry_miss")
            _tr.add_event("state_carry_miss", chunk=str(plan.output_range))
            plan2 = A.derive_task_streams(
                info, w.job.jr, plan.output_range,
                job_idx=w.job.job_idx, task_idx=w.task_idx)
            tmp = TaskItem(w.job, w.task_idx, plan.output_range,
                           plan=plan2, device=w.device)
            try:
                elements2 = self._load_sources(info, tmp, fb_tls)
                return te.execute_task(w.job.jr, plan2, elements2)
            finally:
                self._release_cache(tmp)

    def _evaluate_with_fallback(self, info: A.GraphInfo, te, w: TaskItem,
                                fb_tls):
        """Run a task; on a StateCarryMiss (the affinity chain's premise
        broke — reordering, failed predecessor, different instance)
        re-derive the self-contained plan, reload its sources, and run
        again.  Affinity is an optimization only."""
        if _faults.ACTIVE:
            _faults.inject("pipeline.eval",
                           detail=f"task={w.job.job_idx},{w.task_idx}")
        from .evaluate import StateCarryMiss
        try:
            return te.execute_task(w.job.jr, w.plan, w.elements)
        except StateCarryMiss as e:
            _log.info("task (%d,%d): %s — re-running self-contained",
                      w.job.job_idx, w.task_idx, e)
            self.profiler.count("state_carry_miss")
            _tr.add_event("state_carry_miss")
            w.plan = A.derive_task_streams(
                info, w.job.jr, w.output_range,
                job_idx=w.job.job_idx, task_idx=w.task_idx)
            w.elements = self._load_sources(info, w, fb_tls)
            self._prestage_device_columns(info, w)
            return te.execute_task(w.job.jr, w.plan, w.elements)

    def load_task(self, info: A.GraphInfo, w: TaskItem, tls) -> TaskItem:
        """The load stage: derive the task's row plan and read/decode its
        source elements (shared by the local pipeline and cluster
        workers)."""
        t0 = time.time()
        # success-only, like the evaluate/save stage counters: a failing
        # load must not read as the load stage racing ahead
        out = self._load_task(info, w, tls)
        _M_STAGE_SECONDS.labels(stage="load").inc(time.time() - t0)
        _M_STAGE_TASKS.labels(stage="load").inc()
        return out

    def _load_task(self, info: A.GraphInfo, w: TaskItem, tls) -> TaskItem:
        if _faults.ACTIVE:
            _faults.inject("pipeline.decode",
                           detail=f"task={w.job.job_idx},{w.task_idx}")
        with self.profiler.span("load", level=0, task=w.task_idx,
                                job=w.job.job_idx):
            chain = self._chains.get(w.job.job_idx)
            carry = chain.gate_plan(w.task_idx) if chain is not None \
                else None
            start, end = w.output_range
            wp = int(getattr(w.job.jr, "work_packet_size", 0) or 0)
            if self._stream_packets() and wp > 0 and (end - start) > wp:
                # Work-packet streaming (reference element cache +
                # feeder, evaluate_worker.h:207-218): the task's io
                # packet never materializes whole — per-chunk plans
                # drive an incremental decode -> h2d -> compute
                # pipeline; peak memory is a few chunks, and the h2d of
                # chunk k+1 rides under the compute of chunk k.
                plans = []
                cur = dict(carry) if carry else None
                # the task's chunks run in turn on one evaluator: a
                # bounded-state kernel goes on from the chunk before, so
                # only the rows it has not computed yet are planned (the
                # task's first chunk carries the warm-up)
                bounded = [n.id for n in info.ops
                           if n.bounded_warmup() is not None]
                computed: Dict[int, int] = {}
                for cs in range(start, end, wp):
                    p = A.derive_task_streams(
                        info, w.job.jr, (cs, min(cs + wp, end)),
                        job_idx=w.job.job_idx, task_idx=w.task_idx,
                        carry=cur,
                        computed=computed if cs > start else None)
                    if p.carry_watermarks:
                        cur = dict(cur or {})
                        cur.update(p.carry_watermarks)
                    for nid in bounded:
                        rows = p.streams[nid].compute_rows
                        if len(rows):
                            computed[nid] = int(rows[-1])
                    plans.append(p)
                # a video source whose rows span multiple table items
                # keeps the whole-task path: per-item geometry may
                # differ, which the ragged concat handles and the
                # streaming feed's uniform batches would not
                streamable = True
                for nid in plans[0].source_rows:
                    si = w.job.source_info[nid]
                    if si.get("is_video") and "custom" not in si:
                        desc = si["table"]
                        items = {desc.item_of_row(int(r))
                                 for p in plans
                                 for r in p.source_rows[nid]}
                        if len(items) > 1:
                            streamable = False
                            break
                if streamable:
                    if chain is not None:
                        chain.planned(w.task_idx, cur or {})
                    w.chunk_plans = plans
                    w.plan = None
                    w.elements = None
                    w.chunk_q = queue.Queue(maxsize=2)
                    w.chunk_abort = threading.Event()
                    w.decode_rows = int(sum(
                        len(r) for p in plans
                        for r in p.source_rows.values()))
                    return w
            w.plan = A.derive_task_streams(
                info, w.job.jr, w.output_range,
                job_idx=w.job.job_idx, task_idx=w.task_idx, carry=carry)
            if chain is not None:
                chain.planned(w.task_idx, w.plan.carry_watermarks)
            # sharded gang members: rows owned by neighbor shards are
            # dropped from this member's decode plan BEFORE loading —
            # the loader and frame cache never see them — and restored
            # afterwards so downstream row math stays whole-plan; the
            # halo_fill hook then splices the exchanged boundary rows
            # into the loaded batches (engine/gang.py _make_halo_filler)
            restore: Dict[int, Any] = {}
            if w.halo_drop:
                for nid, drop in w.halo_drop.items():
                    rows = w.plan.source_rows.get(nid)
                    if rows is None or not len(drop):
                        continue
                    restore[nid] = rows
                    w.plan.source_rows[nid] = \
                        rows[~np.isin(rows, drop)]
            w.decode_rows = int(sum(
                len(r) for r in w.plan.source_rows.values()))
            w.elements = self._load_sources(info, w, tls)
            if restore:
                w.plan.source_rows.update(restore)
            if w.halo_fill is not None:
                w.halo_fill(info, w)
            self._prestage_device_columns(info, w)
        return w

    def _stream_packets(self) -> bool:
        import os
        if os.environ.get("SCANNER_TPU_STREAM_PACKETS", "1") \
                in ("0", "false"):
            return False
        return self._stream_opt

    def _prestage_device_columns(self, info: A.GraphInfo, w: TaskItem,
                                 elements: Optional[Dict[int, Any]] = None
                                 ) -> None:
        """Start the host->device transfer of device-bound source columns
        from the LOADER thread.  device_put is async: the copy proceeds
        while this loader decodes the next task and while the evaluator
        computes earlier tasks, so h2d overlaps decode instead of
        serializing at the front of the evaluate stage.  Only columns
        whose every first non-builtin consumer is a device kernel are
        staged — staging a host-kernel input would add a device->host
        round-trip.  The
        target is the chip of the instance this task was assigned to at
        enqueue time (w.device): staging to the default chip for a task
        that instance 3 will evaluate would force a cross-chip copy."""
        from .evaluate import _device_staging_enabled
        if not _device_staging_enabled():
            return
        cols = w.elements if elements is None else elements
        with self.profiler.span("load:prestage", task=w.task_idx,
                                counter=_M_LOAD_PRESTAGE):
            for nid, b in cols.items():
                if self._column_device_bound(info, nid) \
                        and isinstance(b.data, np.ndarray) \
                        and b.data.dtype != object:
                    cols[nid] = b.to_device(w.device)

    def _yuv_device_wire(self, info: A.GraphInfo, node_id: int) -> bool:
        """Should this video column decode to YUV420 wire format?  Yes
        when every first non-builtin consumer is a device kernel (so the
        conversion runs once, on the accelerator) and the backend is an
        accelerator.  SCANNER_TPU_YUV_DEVICE=0 opts out; =force engages
        it on the CPU backend too (tests exercise the full path there)."""
        import os
        flag = os.environ.get("SCANNER_TPU_YUV_DEVICE", "1")
        if flag in ("0", "false"):
            return False
        from .evaluate import _accel_backend
        if flag != "force" and not _accel_backend():
            return False
        return self._column_device_bound(info, node_id)

    def _column_device_bound(self, info: A.GraphInfo, node_id: int) -> bool:
        with self._device_bound_lock:
            cache = self._device_bound_cache
            if cache.get("info") is not info:
                cache.clear()
                cache["info"] = info
            if node_id in cache:
                return cache[node_id]
        by_id = {n.id: n for n in info.ops}
        devices: List[bool] = []
        seen = set()
        frontier = [node_id]
        while frontier:
            nid = frontier.pop()
            if nid in seen:
                continue
            seen.add(nid)
            for cid in info.consumers.get(nid, []):
                c = by_id[cid]
                if c.name == O.OUTPUT_OP:
                    devices.append(False)  # sink fetches to host
                elif c.is_builtin:
                    frontier.append(cid)   # gathers run wherever data is
                else:
                    devices.append(
                        c.effective_device() == DeviceType.TPU)
        res = bool(devices) and all(devices)
        with self._device_bound_lock:
            if self._device_bound_cache.get("info") is info:
                self._device_bound_cache[node_id] = res
        return res

    def _load_sources(self, info: A.GraphInfo, w: TaskItem,
                      tls) -> Dict[int, ColumnBatch]:
        """Read/decode exactly the rows the task needs.  Video sources
        arrive as ONE contiguous (N, H, W, 3) batch straight from the
        decoder — the zero-copy head of the batched data path."""
        out: Dict[int, ColumnBatch] = {}
        for node_id, rows in w.plan.source_rows.items():
            si = w.job.source_info[node_id]
            rows_arr = np.asarray(rows, np.int64)
            rows_l = [int(r) for r in rows]
            if "custom" in si or not si["is_video"]:
                out[node_id] = self._load_plain_source(w, node_id, rows_l)
            elif si["is_video"]:
                # rows are global; multi-item video tables (job outputs)
                # hold one independently-decodable item per task
                desc = si["table"]
                # Device-bound frame columns decode to planar YUV420 and
                # convert to RGB ON the accelerator (kernels/color.py):
                # 1.5 B/px instead of 3 B/px over the host->device link,
                # the first-order term of device pipelines (PERF.md §5;
                # the reference shipped NV12 and converted on-GPU,
                # util/image.cu:22).  SCANNER_TPU_YUV_DEVICE=0 opts out.
                fmt = ("yuv420" if self._yuv_device_wire(info, node_id)
                       else "rgb24")
                # paged frame cache (engine/framecache.py): rows already
                # resident in HBM pages on this task's chip skip decode
                # AND the np->device copy; only miss ranges decode
                cached = self._load_video_cached(info, w, node_id, si,
                                                 rows_l, fmt, tls)
                if cached is not None:
                    out[node_id] = cached
                    continue
                by_item: Dict[int, List[int]] = {}
                for r in rows_l:
                    it = desc.item_of_row(r)
                    start, _ = desc.item_bounds(it)
                    by_item.setdefault(it, []).append(r - start)
                parts: List[ColumnBatch] = []
                for it, local in sorted(by_item.items()):
                    start, _ = desc.item_bounds(it)
                    auto = self._automata(tls, w.job, node_id, si, it,
                                          output_format=fmt)
                    frames = self._decode_counted(auto, local)
                    # convert mark carries THIS item's geometry (items of
                    # one table may differ); mixed-geometry concat falls
                    # back to host conversion in concat_batches
                    convert = (("yuv420", auto.vd.height, auto.vd.width)
                               if fmt == "yuv420" else None)
                    parts.append(ColumnBatch(
                        np.asarray(local, np.int64) + start, frames,
                        convert=convert))
                out[node_id] = concat_batches(parts)
        return out

    def _decode_counted(self, auto, local: List[int]) -> np.ndarray:
        """`auto.get_frames(local)` under the `load:decode` span, with
        the delivered and the codec's frame counts."""
        lbl = threading.current_thread().name
        codec0 = auto.codec_frames
        with self.profiler.span(
                "load:decode", frames=len(local),
                counter=_M_DECODE_SECONDS.labels(loader=lbl)):
            frames = auto.get_frames(local)
        _M_DECODED.labels(loader=lbl).inc(len(local))
        _M_CODEC_FRAMES.labels(loader=lbl).inc(auto.codec_frames - codec0)
        return frames

    def _load_plain_source(self, w: TaskItem, node_id: int,
                           rows_l: List[int]) -> ColumnBatch:
        """Non-video source rows: custom-storage reads or column loads
        (shared by the whole-task and per-chunk streaming loaders)."""
        si = w.job.source_info[node_id]
        rows_arr = np.asarray(rows_l, np.int64)
        if "custom" in si:
            vals = si["custom"].storage.read_rows(si["custom"], rows_l)
            return ColumnBatch.from_elements(rows_arr, vals)
        from ..storage.streams import decode_element
        desc = si["table"]
        vals = list(self.db.load_column(
            desc.id, si["column"], rows=rows_l,
            sparsity_threshold=w.job.sparsity_threshold))
        codec = si.get("codec", "raw")
        return ColumnBatch.from_elements(
            rows_arr, [decode_element(v, codec) for v in vals])

    def _cache_eligible(self, info: A.GraphInfo, node_id: int) -> bool:
        """Frame-cache eligibility for one video column: the cache is
        an HBM pool, so only device-staged columns qualify — and only
        when the kill switch is up ([perf] frame_cache_enabled)."""
        from .evaluate import _device_staging_enabled
        return _fc.enabled() and _device_staging_enabled() \
            and self._column_device_bound(info, node_id)

    @staticmethod
    def _keyint_of(si) -> int:
        """Keyframe-interval estimate for page sizing (pages should map
        onto GOP-decodable units); 0 = unknown."""
        vd = si.get("video_meta")
        ki = getattr(vd, "keyframe_indices", None) if vd is not None \
            else None
        if ki is not None and len(ki) > 1:
            return int(np.median(np.diff(np.asarray(ki, np.int64))))
        return 0

    def _load_video_cached(self, info: A.GraphInfo, w: TaskItem,
                           node_id: int, si, rows_l: List[int], fmt: str,
                           tls) -> Optional[ColumnBatch]:
        """The cache-consulting flavor of the whole-task video load:
        plan (pin resident pages on this task's chip), decode only the
        miss rows, offer them toward page completion, and assemble the
        task's column as a page-table gather.  None = ineligible or
        bypassed — the caller runs the direct decode+stage path."""
        if not self._cache_eligible(info, node_id) or not rows_l:
            return None
        desc = si["table"]
        items = {desc.item_of_row(int(r)) for r in rows_l}
        if len(items) != 1:
            # per-item geometry may differ (the ragged-concat path);
            # pages are per-item, so a multi-item task stays direct
            return None
        item = items.pop()
        start, end = desc.item_bounds(item)
        local = np.asarray(rows_l, np.int64) - start
        cache = _fc.cache()
        plan = cache.plan(w.device, (self._cache_db_key, desc.id),
                          si["column"], item, fmt, local,
                          total_rows=end - start,
                          keyint=self._keyint_of(si))
        # pin BEFORE decoding: a decode failure routes through
        # task_failed -> _release_cache, and the finalizer backstops
        _fc.attach_lease(w, plan.lease)
        miss = plan.miss_rows
        hw = plan.hw
        if len(miss):
            auto = self._automata(tls, w.job, node_id, si, item,
                                  output_format=fmt)
            frames = self._decode_counted(auto, miss.tolist())
            hw = (auto.vd.height, auto.vd.width)
        else:
            frames = np.zeros((0, 1), np.uint8)
        if fmt == "yuv420" and not (hw and hw[0]):
            return None  # no geometry for the convert mark: bypass
        try:
            with self.profiler.span("load:stage", counter=_M_LOAD_STAGE,
                                    rows=len(rows_l), fresh=len(miss)):
                data = cache.assemble(plan, miss, frames, hw=hw)
        except _fc.CacheBypass:
            # falling back here re-decodes the miss rows on the direct
            # path (double decode for this one task).  Acceptable: a
            # bypass after plan() requires a pinned hit row to vanish,
            # which pinning exists to prevent — this is a correctness
            # backstop, not a path with a cost budget.
            return None
        convert = (("yuv420", hw[0], hw[1]) if fmt == "yuv420" else None)
        return ColumnBatch(np.asarray(rows_l, np.int64), data,
                           convert=convert)

    def _release_cache(self, w: TaskItem) -> None:
        """Unpin the task's frame-cache pages (evaluation is done with
        them, or the task failed/was revoked).  Idempotent; leases a
        dropped TaskItem never reaches release on are backstopped by
        the finalizer attach_lease installed."""
        leases, w.cache_leases = w.cache_leases, None
        for lease in leases or ():
            lease.release()

    def _automata(self, tls, job: JobContext, node_id: int, si,
                  item: int = 0, output_format: str = "rgb24"):
        cache = getattr(tls, "automata", None)
        if cache is None:
            cache = {}
            tls.automata = cache
        key = (job.job_idx, node_id, item, output_format)
        if key not in cache:
            from ..video.automata import DecoderAutomata
            desc = si["table"]
            if item == 0:
                vd = si["video_meta"]
            else:
                vd = md.VideoDescriptor.deserialize(self.db.backend.read(
                    md.video_meta_path(desc.id, si["column"], item)))
            cache[key] = DecoderAutomata(
                self.db.backend, vd,
                md.column_item_path(desc.id, si["column"], item),
                n_threads=self.decoder_threads,
                output_format=output_format)
        return cache[key]

    def _save_task(self, info: A.GraphInfo, w: TaskItem) -> None:
        """Encode + write one item per sink (reference save_worker.cpp +
        PostEvaluateWorker video encode, evaluate_worker.cpp:1373-1560)."""
        if _faults.ACTIVE:
            _faults.inject("pipeline.save",
                           detail=f"task={w.job.job_idx},{w.task_idx}")
        start, end = w.output_range
        for sink in info.sinks:
            if sink.id in w.job.custom_sinks:
                stream = w.job.custom_sinks[sink.id]
                rows = self._fetch_sink(w, sink.id)
                with self.profiler.span("save:write", task=w.task_idx):
                    stream.storage.write_item(stream, start, rows)
                continue
            if sink.id not in w.job.sink_tables:
                continue
            desc, col_name, codec, enc_opts = w.job.sink_tables[sink.id]
            rows = self._fetch_sink(w, sink.id)
            t_write = time.time()
            item_idx = w.task_idx
            if codec == "frame":
                mode = "video" if self._is_encodable(rows) else "pickle"
                with w.job.sink_mode_lock:
                    prev = w.job.sink_modes.get(sink.id)
                    if prev is None:
                        # cross-worker guard: exactly one writer (across all
                        # processes) creates the durable marker; everyone
                        # else reads the winner's mode (distributed savers
                        # share no process state)
                        marker = f"{md.table_dir(desc.id)}/.{col_name}.mode"
                        if self.db.backend.write_exclusive(
                                marker, mode.encode()):
                            prev = mode
                        else:
                            prev = self.db.backend.read(marker).decode()
                        w.job.sink_modes[sink.id] = prev
                    if prev != mode:
                        raise JobException(
                            f"{desc.name}: mixed frame output types across "
                            f"tasks ({prev} vs {mode}); kernels must "
                            f"produce a consistent frame dtype")
                    if mode == "pickle":
                        self._demote_video_column(desc)
                if mode == "video":
                    self._write_video_item(w.job, desc, col_name, item_idx,
                                           rows, enc_opts)
                else:
                    # non-uint8/RGB frame data (e.g. float32 flow fields):
                    # the reference stores these as RAW-format video
                    # columns; here the column degrades to pickled arrays.
                    # A row pays its bytes (16,588,800 for a 1080p flow
                    # field) three times on this thread: the pickle's copy
                    # (one memcpy: a row fetched from a device is a
                    # contiguous view, ColumnBatch.prefetch_host; a
                    # strided one a host kernel hands over is copied
                    # element by element, under the interpreter lock),
                    # the item's checksum and the item's join, then the
                    # backend's write (span save:raw; one contiguous buffer
                    # a task would pay the write alone)
                    import pickle
                    span = self.profiler.span
                    with span("save:raw", counter=_M_RAW_FRAME_SECONDS,
                              task=w.task_idx, rows=len(rows)):
                        with span("raw:pickle", counter=_M_RAW_PICKLE):
                            blobs = [e if isinstance(e, NullElement)
                                     else pickle.dumps(
                                         np.asarray(e),
                                         protocol=pickle.HIGHEST_PROTOCOL)
                                     for e in rows]
                        with span("raw:build", counter=_M_RAW_BUILD):
                            item = IT.build_item(blobs)
                            del blobs
                        with span("raw:write", counter=_M_RAW_WRITE,
                                  bytes=len(item)):
                            self.db.backend.write(
                                md.column_item_path(desc.id, col_name,
                                                    item_idx), item)
                    _M_RAW_FRAME_BYTES.inc(len(item))
            else:
                blobs = []
                for e in rows:
                    if isinstance(e, NullElement):
                        blobs.append(e)
                    elif codec == "raw":
                        if not isinstance(e, (bytes, bytearray, memoryview)):
                            raise JobException(
                                f"{desc.name}: raw column got "
                                f"{type(e).__name__}")
                        blobs.append(bytes(e))
                    else:
                        import pickle
                        blobs.append(pickle.dumps(
                            e, protocol=pickle.HIGHEST_PROTOCOL))
                IT.write_item(self.db.backend,
                              md.column_item_path(desc.id, col_name,
                                                  item_idx), blobs)
            self.profiler.add_interval("save:write", t_write, time.time(),
                                       task=w.task_idx)

    @staticmethod
    def _async_sink_fetch_enabled() -> bool:
        """SCANNER_TPU_ASYNC_SINK_FETCH=0 opts out of starting sink
        device->host copies at eval-done (the fetch then blocks in the
        saver, the pre-affinity behavior; the ordering test A/Bs it)."""
        import os
        return os.environ.get("SCANNER_TPU_ASYNC_SINK_FETCH", "1") \
            not in ("0", "false")

    @staticmethod
    def _count_sink_rows(batches) -> Dict[str, int]:
        """Device sink batches' rows by how they reach the host
        (`ColumnBatch.sink_layout`), counted once a batch: where its
        copy is started, else where it is fetched."""
        counts = {"relaid": 0, "asis": 0}
        for b in batches:
            layout = b.sink_layout if isinstance(b, ColumnBatch) else None
            if layout is not None:
                counts[layout] += len(b)
                _M_SINK_ROWS.labels(layout=layout).inc(len(b))
        return counts

    def _prefetch_results(self, w: TaskItem) -> Dict[str, int]:
        """Kick off the async device->host copy of every sink batch the
        moment evaluation finishes — hung off the TaskItem before it
        enters save_q, so task k's d2h latency rides under task
        k+1's evaluation instead of serializing inside the saver.  A
        batch the chip holds off row-major is laid out row-major first
        (`ColumnBatch.prefetch_host`).  Returns the rows by layout, the
        `evaluate:prefetch` span's args."""
        if not w.results or not self._async_sink_fetch_enabled():
            return {}
        for b in w.results.values():
            if isinstance(b, ColumnBatch):
                b.prefetch_host()
        return self._count_sink_rows(w.results.values())

    def _fetch_sink(self, w: TaskItem, sink_id: int) -> List[Any]:
        """The single device->host fetch of the batched data path: one
        sink's rows of the task's output range, on the host."""
        batch = w.results[sink_id]
        with self.profiler.span("save:fetch", counter=_M_SINK_FETCH_SECONDS,
                                task=w.task_idx):
            if not self._async_sink_fetch_enabled():
                self._count_sink_rows([batch])
            host = batch.to_host()
            if host is not batch:
                _M_SINK_FETCH_BYTES.inc(host.data.nbytes)
            return host.take_range(*w.output_range).elements()

    @staticmethod
    def _sink_rows(batch, start: int, end: int) -> List[Any]:
        """Materialize a sink ColumnBatch's rows [start, end) as host
        elements (array rows become views).  The whole batch is fetched
        FIRST — completing the async copy _prefetch_results started at
        eval-done (a device-side slice would be a fresh array the
        prefetch never covered) — then the contiguous range takes
        ColumnBatch.take_range's direct-slice fast path on host."""
        return batch.to_host().take_range(start, end).elements()

    @staticmethod
    def _is_encodable(rows: List[Any]) -> bool:
        """True when the item is H.264-encodable (uint8 RGB).  Null rows in
        an otherwise-encodable item raise inside _write_video_item, matching
        the reference where video columns cannot hold nulls."""
        saw_frame = False
        for e in rows:
            if isinstance(e, NullElement):
                continue
            a = np.asarray(e)
            if a.dtype != np.uint8 or a.ndim != 3 or a.shape[2] != 3:
                return False
            saw_frame = True
        return saw_frame

    def _demote_video_column(self, desc: md.TableDescriptor) -> None:
        col = desc.columns[0]
        already = (col.type == md.ColumnType.BYTES and col.codec == "pickle")
        if not already:
            col.type = md.ColumnType.BYTES
            col.codec = "pickle"
            self.db.write_table_descriptor(desc)

    def _write_video_item(self, job: JobContext, desc: md.TableDescriptor,
                          col_name: str, item_idx: int, rows: List[Any],
                          enc_opts: Dict) -> None:
        from ..video.lib import Encoder
        frames = []
        for e in rows:
            if isinstance(e, NullElement):
                raise JobException(
                    f"{desc.name}: video output cannot store null rows; "
                    f"use a blob column")
            a = np.asarray(e)
            if a.dtype != np.uint8 or a.ndim != 3 or a.shape[2] != 3:
                raise JobException(
                    f"{desc.name}: video output requires uint8 HxWx3 "
                    f"frames, got {a.dtype} {a.shape}")
            frames.append(a)
        h, w_ = frames[0].shape[:2]
        keyint = int(enc_opts.get("keyint", 16))
        enc = None
        try:
            with self.profiler.span("save:encode",
                                    counter=_M_ENCODE_SECONDS,
                                    item=item_idx, frames=len(frames)
                                    ) as span:
                enc = Encoder(w_, h, fps=job.fps or 30.0, codec="libx264",
                              bitrate=int(enc_opts.get("bitrate", 0)),
                              crf=int(enc_opts.get("crf", 20)),
                              keyint=keyint)
                # the encoder wants a frame contiguous.  A row fetched
                # from a device is (its sink batch was laid out
                # row-major there, ColumnBatch.prefetch_host) and comes
                # back as it is; a host kernel may hand over a strided
                # view, which is copied here, just before its feed so
                # that the encoder reads it warm.  Timed frame by frame
                # into one number an item: it has a counter and no span
                # of its own
                copying = 0.0
                for f in frames:
                    t0 = time.time()
                    f = np.ascontiguousarray(f)
                    copying += time.time() - t0
                    enc.feed(f)
                span.args["contiguous_s"] = round(copying, 6)
                _M_CONTIGUOUS_SECONDS.inc(copying)
                enc.flush()
                data, sizes, keys, pts, dts = enc.take_packets()
            _M_ENCODED_FRAMES.inc(len(frames))
            _M_ENCODED_BYTES.inc(len(data))
            vd = md.VideoDescriptor(
                width=w_, height=h, fps=job.fps or 30.0,
                num_frames=len(frames), codec="h264",
                extradata=enc.extradata,
                sample_offsets=np.concatenate(
                    [[0], np.cumsum(sizes[:-1])]).astype(np.uint64)
                if len(sizes) else np.zeros(0, np.uint64),
                sample_sizes=sizes.astype(np.uint64),
                keyframe_indices=np.nonzero(keys)[0].astype(np.int64),
                sample_pts=pts, sample_dts=dts,
                tb_num=enc.fps_den, tb_den=enc.fps_num)
            self.db.backend.write(
                md.column_item_path(desc.id, col_name, item_idx), data)
            self.db.backend.write(
                md.video_meta_path(desc.id, col_name, item_idx),
                vd.serialize())
        finally:
            if enc is not None:
                enc.close()
