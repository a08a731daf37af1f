"""Batched column data for task evaluation.

The reference keeps per-element buffers from a pooled block allocator and
re-packs them into batches at each kernel call
(scanner/util/memory.cpp:269 BlockAllocator,
scanner/engine/evaluate_worker.cpp:1040-1100 batching).  On TPU the natural
design is stronger: a task's column is ONE contiguous array the whole way —
decoded straight into a batch buffer, moved host->device once, sliced (not
copied) into kernel calls, chained op-to-op as device arrays, and fetched
back exactly once at the sink.

`ColumnBatch` is that representation.  `data` is one of
  - ``np.ndarray``  — host batch, axis 0 = rows (uniform frames/blobs)
  - ``jax.Array``   — device batch, axis 0 = rows
  - ``list``        — arbitrary python objects (ragged frames, tuples, ...)
plus a sorted ``rows`` vector naming the (stream-local or global) row ids
and an optional ``nulls`` mask.  Gathers/slices on array data are views or
device ops; nothing round-trips through per-row python objects unless a
per-row (batch=1, non-array) consumer asks for it.
"""

from __future__ import annotations

import functools
from types import SimpleNamespace
from typing import Any, List, Optional, Sequence, Union

import numpy as np

from ..common import NullElement
from ..util import faults as _faults
from ..util import memstats as _ms
from ..util import metrics as _mx

Elem = Any

# host<->device traffic as live counters, bytes only.  The h2d
# device_put call returns at dispatch and the copy completes under
# later compute by design, so host seconds around it time an enqueue
# (the loader's `load:stage` and `load:prestage` spans show what the
# dispatch costs the host); a blocking d2h fetch is timed where it has
# a name: the saver's `save:fetch`, a host op's `evaluate:window`.
_M_H2D_BYTES = _mx.registry().counter(
    "scanner_tpu_h2d_bytes_total",
    "Bytes staged host->device via ColumnBatch.to_device.")
_M_D2H_BYTES = _mx.registry().counter(
    "scanner_tpu_d2h_bytes_total",
    "Bytes fetched device->host via ColumnBatch.to_host.")


def staged_device_put(host: "np.ndarray", device, kind: str,
                      fault_detail: str):
    """The ONE engine host->device staging contract: the
    memory.pressure fault site, RESOURCE_EXHAUSTED forensics
    (site=staging), the shared h2d byte meter, and an
    allocation-ledger registration under `kind`.  Used by to_device
    AND the frame cache's fresh-row staging (engine/framecache.py), so
    the chaos/forensics/metering behavior of the two paths can never
    drift — and a cache-on/off A/B of `scanner_tpu_h2d_bytes_total`
    bills the same meter on both sides."""
    import jax
    lbl = _ms.device_label(device)
    try:
        if _faults.ACTIVE:
            _faults.inject("memory.pressure", detail=fault_detail)
        data = jax.device_put(host, device)
    except Exception as e:
        if _ms.is_oom(e):
            _ms.note_oom(e, site="staging",
                         detail=f"h2d {host.nbytes} bytes -> {lbl}")
        raise
    _M_H2D_BYTES.inc(host.nbytes)
    _ms.track_array(data, kind,
                    device=lbl if device is not None else None)
    return data


@functools.lru_cache(maxsize=None)
def row_programs(scope: str) -> SimpleNamespace:
    """The row-axis device programs of the batched data path, each a
    jitted function traced under `jax.named_scope(scope)`: the device
    trace's operations then carry the name of the layer that dispatched
    them (`columnbatch` here, `framecache` in engine/framecache.py;
    docs/profiling.md "Device side"), where the same operations
    dispatched eagerly carried none.  The programs are the ones eager
    indexing compiled: one a (shape, start, length) for a run of rows
    (`slice`, and `copy`, whose result never shares the operand's
    buffer), one an (array shape, index length) for rows picked by
    index (`gather`), one a tuple of shapes for arrays joined end to
    end (`concat`), and one a (shape, merged shape) for a sink batch
    laid out row-major before its copy to the host (`rowmajor`:
    `data.reshape(merged_row_shape)`, whose result the chip keeps
    row-major and dense; `ColumnBatch.prefetch_host`).  It is written
    as the two transpositions the compiler makes of that reshape of a
    planar array (the merged dimensions brought to the front, where
    merging them moves nothing, and back): as a bare reshape the first
    of them is a copy of the parameter, which carries the parameter's
    name and no scope.  `copy` adds a zero it is handed at run time
    (`copy(data, start, n, zero)`, a 0-d array of the data's type): a
    copy alone lowers to nothing, the compiler copies the unchanged
    parameter itself, and that operation carries no name (the whole
    block a fill keeps, a twelfth of `hist_dense`'s busy time); the add
    reads and writes the same bytes once, under the scope."""
    import jax
    import jax.numpy as jnp
    from jax import lax

    def scoped(fn, **kw):
        return jax.jit(jax.named_scope(scope)(fn), **kw)

    def slice_rows(data, start, n):
        return lax.slice_in_dim(data, start, start + n, axis=0)

    def copy_rows(data, start, n, zero):
        return slice_rows(data, start, n) + zero

    def gather_rows(data, idx):
        return data[idx]

    def concat_rows(*parts):
        return jnp.concatenate(parts, axis=0)

    def rowmajor_rows(data, shape):
        lead = len(shape) - 1
        front = jnp.transpose(
            data, tuple(range(lead, data.ndim)) + tuple(range(lead)))
        return jnp.transpose(front.reshape(shape[-1:] + shape[:-1]),
                             tuple(range(1, lead + 1)) + (0,))

    return SimpleNamespace(
        slice=scoped(slice_rows, static_argnums=(1, 2)),
        copy=scoped(copy_rows, static_argnums=(1, 2)),
        gather=scoped(gather_rows), concat=scoped(concat_rows),
        rowmajor=scoped(rowmajor_rows, static_argnums=(1,)))


def rows_run(data, start: int, n: int):
    """Rows [start, start + n) of array data or a list: a view of a
    host array, the array itself where that is all of it, else one
    program on the device."""
    if not _is_jax(data):
        return data[start:start + n]
    if start == 0 and n == data.shape[0]:
        return data
    return row_programs("columnbatch").slice(data, start, n)


def rows_at(data, idx: np.ndarray):
    """Rows of array data picked by index: a numpy copy on the host,
    one gather program on the device."""
    if not _is_jax(data):
        return data[idx]
    return row_programs("columnbatch").gather(data, idx)


# the chip keeps a minor dimension at least this long row-major and
# dense (a vector register's lanes); a shorter one it lays out as planes
_MINOR_LEN = 128


def merged_row_shape(shape: tuple) -> tuple:
    """`shape` with its trailing dimensions merged until the minor one
    is long (the row axis stays): (n, 1080, 1920, 3) -> (n, 1080, 5760).
    The same elements in the same row-major order, so the host's
    reshape back is a view."""
    dims = list(shape)
    while len(dims) > 2 and dims[-1] < _MINOR_LEN:
        dims[-2:] = [dims[-2] * dims[-1]]
    return tuple(dims)


def off_row_major(data) -> bool:
    """True where a device array's own layout is not row-major: a
    frame column on the chip is planar (minor-to-major W, H, C, N: the
    compiler keeps no minor dimension of 3), and `np.asarray` of it
    keeps that order in its strides.  The one condition of the sink
    relayout; a CPU backend always reads row-major."""
    layout = data.format.layout
    return layout is not None and \
        tuple(layout.major_to_minor) != tuple(range(data.ndim))


def _relaid_shape(data) -> Optional[tuple]:
    """The shape a device array is relaid to before its copy to the
    host, None where it goes as it is: laid out row-major already, or
    with no trailing dimensions to merge."""
    if not off_row_major(data):
        return None
    merged = merged_row_shape(data.shape)
    return None if merged == tuple(data.shape) else merged


def _row_major(data):
    """`data` as the array whose copy to the host arrives row-major:
    itself, or one `rowmajor` program's result."""
    merged = _relaid_shape(data)
    if merged is None:
        return data
    return row_programs("columnbatch").rowmajor(data, merged)


def _is_jax(x) -> bool:
    # cheap structural check that avoids importing jax for pure-host runs
    return type(x).__module__.startswith("jax")


def is_array_data(data) -> bool:
    return isinstance(data, np.ndarray) or _is_jax(data)


class ColumnBatch:
    """One column of one task: row ids + batched data (+ null mask).

    ``convert`` marks data stored in a pre-conversion wire format:
    ``("yuv420", h, w)`` means rows are flat planar I420 frames staged at
    1.5 B/px; ``converted()`` turns them into (n, h, w, 3) RGB where the
    data lives (device op for jax arrays, numpy for host).  Row-axis
    transforms (take/relabel/concat) preserve the mark — builtin gathers
    never look inside a frame — and any per-row host materialization
    converts transparently so no consumer can observe raw YUV bytes.

    ``_row_shape`` marks device data that `prefetch_host` laid out
    row-major for its copy to the host: `data` then holds each row with
    its trailing dimensions merged (`merged_row_shape`) and
    ``_row_shape`` is the row's own shape, which `to_host` gives back.
    Evaluation is done with such a batch: `to_host` is all it is for.
    """

    __slots__ = ("rows", "data", "nulls", "convert", "_row_pos",
                 "_row_shape")

    def __init__(self, rows: np.ndarray, data,
                 nulls: Optional[np.ndarray] = None,
                 convert: Optional[tuple] = None):
        self.rows = np.asarray(rows, np.int64)
        self.data = data
        self.nulls = nulls if nulls is None or nulls.any() else None
        self.convert = convert
        self._row_pos = None
        self._row_shape = None
        if not is_array_data(data) and len(data) != len(self.rows):
            raise ValueError(
                f"ColumnBatch: {len(data)} elements for {len(self.rows)} rows")
        if len(self.rows) > 1 and (np.diff(self.rows) <= 0).any():
            raise ValueError("ColumnBatch rows must be strictly increasing")

    # -- construction ---------------------------------------------------

    @staticmethod
    def from_elements(rows: Sequence[int], elems: Sequence[Elem]
                      ) -> "ColumnBatch":
        """Build from per-row elements; packs uniform ndarrays into one
        host batch, otherwise stores the object list."""
        rows = np.asarray(list(rows), np.int64)
        elems = list(elems)
        nulls = np.array([isinstance(e, NullElement) or e is None
                          for e in elems], bool)
        if nulls.all():
            return ColumnBatch(rows, [NullElement()] * len(elems), nulls)
        live = [e for e, n in zip(elems, nulls) if not n]
        first = live[0]
        if (isinstance(first, np.ndarray)
                and all(isinstance(e, np.ndarray) and e.shape == first.shape
                        and e.dtype == first.dtype for e in live)):
            if not nulls.any() and len(live) == len(elems):
                return ColumnBatch(rows, np.stack(elems))
            batch = np.zeros((len(elems),) + first.shape, first.dtype)
            batch[~nulls] = np.stack(live)
            return ColumnBatch(rows, batch, nulls)
        return ColumnBatch(rows, elems, nulls if nulls.any() else None)

    # -- row lookup -----------------------------------------------------

    def positions(self, rows: np.ndarray) -> np.ndarray:
        """Positions of `rows` (must all be present) in this batch."""
        pos = np.searchsorted(self.rows, rows)
        if (pos >= len(self.rows)).any() or (self.rows[pos] != rows).any():
            missing = sorted(set(np.asarray(rows).tolist())
                             - set(self.rows.tolist()))
            raise KeyError(f"rows not in batch: {missing[:5]}...")
        return pos

    # -- transforms (device-aware; views/slices where possible) ---------

    def take(self, positions: np.ndarray,
             new_rows: np.ndarray) -> "ColumnBatch":
        """Gather positions (−1 ⇒ null row) and relabel to new_rows."""
        positions = np.asarray(positions, np.int64)
        new_rows = np.asarray(new_rows, np.int64)
        neg = positions < 0
        nulls = None
        if self.nulls is not None:
            nulls = np.where(neg, True, self.nulls[np.where(neg, 0,
                                                            positions)])
        elif neg.any():
            nulls = neg
        safe = np.where(neg, 0, positions)
        if isinstance(self.data, np.ndarray):
            # contiguous slice stays a view
            if (not neg.any() and len(safe)
                    and np.array_equal(safe,
                                       np.arange(safe[0],
                                                 safe[0] + len(safe)))):
                data = self.data[safe[0]:safe[0] + len(safe)]
            else:
                data = self.data[safe]
        elif _is_jax(self.data):
            data = rows_at(self.data, safe)  # on-device gather
        else:
            data = [NullElement() if neg[i] else self.data[int(p)]
                    for i, p in enumerate(safe)]
        return ColumnBatch(new_rows, data, nulls, convert=self.convert)

    def _contig_slice(self, start_row: int, k: int,
                      new_rows: np.ndarray) -> Optional["ColumnBatch"]:
        """Slice rows [start_row, start_row+k) directly if they are all
        present and contiguous in this batch (two binary-searched
        endpoint checks instead of a full positions lookup; array data
        stays a view).  None = not contiguous here, use the slow path."""
        p0 = int(np.searchsorted(self.rows, start_row))
        if p0 + k > len(self.rows) or self.rows[p0] != start_row \
                or self.rows[p0 + k - 1] != start_row + k - 1:
            return None
        nulls = self.nulls[p0:p0 + k] if self.nulls is not None else None
        return ColumnBatch(new_rows, rows_run(self.data, p0, k), nulls,
                           convert=self.convert)

    def take_rows(self, rows: np.ndarray,
                  new_rows: Optional[np.ndarray] = None) -> "ColumnBatch":
        rows = np.asarray(rows, np.int64)
        nr = rows if new_rows is None else new_rows
        # contiguous [start, end) fast path — the sink hot path fetches
        # exactly this shape once per task
        k = len(rows)
        if k and len(self.rows) and int(rows[-1]) - int(rows[0]) == k - 1 \
                and (k == 1 or bool((np.diff(rows) == 1).all())):
            out = self._contig_slice(int(rows[0]), k, nr)
            if out is not None:
                return out
        return self.take(self.positions(rows), nr)

    def take_range(self, start: int, end: int) -> "ColumnBatch":
        """take_rows for the contiguous row range [start, end) without
        the caller materializing an index or this batch running the full
        positions lookup (executor._sink_rows hot path)."""
        rows = np.arange(start, end, dtype=np.int64)
        if len(rows) and len(self.rows):
            out = self._contig_slice(int(start), len(rows), rows)
            if out is not None:
                return out
        return self.take(self.positions(rows), rows)

    def relabel(self, new_rows: np.ndarray) -> "ColumnBatch":
        """Same data, new row ids (slice/unslice row renumbering)."""
        return ColumnBatch(new_rows, self.data, self.nulls,
                           convert=self.convert)

    # -- device movement ------------------------------------------------

    def to_device(self, device=None) -> "ColumnBatch":
        """Host -> device, one async transfer for the whole batch.
        `device` targets a specific chip (evaluator affinity: instance
        *i* stages to chip *i*); None keeps jax's default placement.  A
        batch already on device is re-staged only when it sits on a
        DIFFERENT chip than the requested one — the copy the old
        implicit-default path used to trigger silently inside the jitted
        call now happens here, visibly, and only when asked for.
        A convert-marked batch ships its WIRE format (that is the point:
        1.5 B/px over the link, convert on device via converted())."""
        if isinstance(self.data, np.ndarray):
            # the full staging contract — fault site, OOM forensics,
            # h2d meters, ledger registration — lives in ONE place
            # shared with the frame cache's staging path
            data = staged_device_put(
                self.data, device, "staging",
                fault_detail=f"h2d:{_ms.device_label(device)}:"
                             f"{self.data.nbytes}")
            return ColumnBatch(self.rows, data,
                               self.nulls, convert=self.convert)
        if device is not None and _is_jax(self.data):
            cur = None
            devs = getattr(self.data, "devices", None)
            if callable(devs):
                try:
                    cur = set(devs())
                except Exception:  # noqa: BLE001 — version drift
                    cur = None
            if cur is not None and cur != {device}:
                import jax
                try:
                    data = jax.device_put(self.data, device)
                except Exception as e:
                    if _ms.is_oom(e):
                        _ms.note_oom(
                            e, site="staging",
                            detail=f"cross-chip re-stage -> "
                                   f"{_ms.device_label(device)}")
                    raise
                _ms.track_array(data, "staging",
                                device=_ms.device_label(device))
                return ColumnBatch(self.rows, data,
                                   self.nulls, convert=self.convert)
        return self

    def prefetch_host(self) -> "ColumnBatch":
        """Start this batch's device->host copy WITHOUT blocking (the
        async half of the sink fetch): called at eval-done so the d2h
        latency of task k rides under the evaluation of task k+1
        instead of serializing inside the saver (PERF.md §3).  The
        later to_host() then finds the transfer done (or in flight) and
        returns quickly.  No-op for host data; best-effort on jax
        versions without copy_to_host_async.

        A batch the chip does not hold row-major is laid out so first,
        by one device program, and keeps that array in place of the
        planar one (which is released as soon as the program has run):
        the host then receives its bytes in the order every consumer
        wants, where a saver paid an element-by-element copy of each
        planar frame (PERF.md §6, PR 39).  Decided from the array's
        own layout; a convert-marked batch ships its wire as it is.
        The caller is done with the batch but for its to_host()."""
        if _is_jax(self.data):
            if self.convert is None and self._row_shape is None:
                relaid = _row_major(self.data)
                if relaid is not self.data:
                    self._row_shape = tuple(self.data.shape[1:])
                    self.data = relaid
            # the sink batch sits in device memory until the saver's
            # fetch: account it so pre-fetch HBM pressure has an owner
            _ms.track_array(self.data, "sink")
            fn = getattr(self.data, "copy_to_host_async", None)
            if fn is not None:
                try:
                    fn()
                except Exception:  # noqa: BLE001 — purely an overlap hint
                    pass
        return self

    @property
    def sink_layout(self) -> Optional[str]:
        """How this batch's rows reach the host: "relaid" (laid out
        row-major on the device first: by `prefetch_host` already, or
        by the `to_host` to come), "asis" (fetched in the layout they
        have), None for host data."""
        if not _is_jax(self.data):
            return None
        relaid = self._row_shape is not None or (
            self.convert is None and _relaid_shape(self.data) is not None)
        return "relaid" if relaid else "asis"

    def to_host(self) -> "ColumnBatch":
        """Materialize device data on host (the single sink-side fetch):
        a C-contiguous array whose rows are contiguous views.  Where
        nothing was prefetched the row-major layout is made here, into
        an array that lives for the copy alone: this batch is left as
        it is (a host op's input may feed a device op next)."""
        if _is_jax(self.data):
            src, row_shape = self.data, self._row_shape
            if row_shape is None:
                row_shape = tuple(src.shape[1:])
                if self.convert is None:
                    src = _row_major(src)
            data = np.asarray(src).reshape((len(src),) + row_shape)
            _M_D2H_BYTES.inc(data.nbytes)
            return ColumnBatch(self.rows, data, self.nulls,
                               convert=self.convert)
        return self

    def converted(self) -> "ColumnBatch":
        """Resolve a pending wire-format conversion (no-op otherwise).
        jax data converts with the jit device op, host arrays with the
        bit-identical numpy flavor (kernels/color.py)."""
        if self.convert is None:
            return self
        kind, h, w = self.convert
        if kind != "yuv420":
            raise ValueError(f"unknown convert mark {self.convert!r}")
        from ..kernels.color import yuv420_to_rgb_device, yuv420_to_rgb_host
        if _is_jax(self.data):
            data = yuv420_to_rgb_device(self.data, h, w)
        elif isinstance(self.data, np.ndarray):
            data = yuv420_to_rgb_host(self.data, h, w)
        else:
            raise ValueError(
                "convert-marked batch holds non-array data")
        return ColumnBatch(self.rows, data, self.nulls)

    # -- per-row access (host materialization boundary) -----------------

    def __len__(self) -> int:
        return len(self.rows)

    def is_null_pos(self, pos: int) -> bool:
        return self.nulls is not None and bool(self.nulls[pos])

    def element_at(self, pos: int) -> Elem:
        """Element at position `pos` (a view for host arrays; a
        convert-marked batch yields the CONVERTED row — raw wire bytes
        are never observable per-row)."""
        if self.is_null_pos(pos):
            return NullElement()
        if self.convert is not None:
            from ..kernels.color import yuv420_to_rgb_host
            _kind, h, w = self.convert
            row = np.asarray(self.data[pos])
            return yuv420_to_rgb_host(row, h, w)
        if _is_jax(self.data):
            return np.asarray(self.data[pos])
        return self.data[pos]

    def elements(self) -> List[Elem]:
        """All elements as per-row host objects (sink/write boundary)."""
        host = self.to_host()
        return [host.element_at(i) for i in range(len(host))]

    def get_row(self, row: int) -> Elem:
        return self.element_at(int(self.positions(
            np.asarray([row], np.int64))[0]))


def concat_batches(parts: List[ColumnBatch]) -> ColumnBatch:
    """Concatenate row-disjoint batches (already in row order)."""
    if len(parts) == 1:
        return parts[0]
    converts = {p.convert for p in parts}
    if len(converts) > 1:
        # mixed wire formats (shouldn't happen within one column; be safe)
        parts = [p.converted() for p in parts]
        converts = {None}
    convert = next(iter(converts))
    rows = np.concatenate([p.rows for p in parts])
    nulls = None
    if any(p.nulls is not None for p in parts):
        nulls = np.concatenate(
            [p.nulls if p.nulls is not None else np.zeros(len(p), bool)
             for p in parts])
    datas = [p.data for p in parts]
    if all(isinstance(d, np.ndarray) for d in datas) and \
            len({(d.shape[1:], d.dtype) for d in datas}) == 1:
        return ColumnBatch(rows, np.concatenate(datas), nulls,
                           convert=convert)
    if all(_is_jax(d) for d in datas):
        if len({(tuple(d.shape[1:]), d.dtype) for d in datas}) == 1:
            return ColumnBatch(
                rows, row_programs("columnbatch").concat(*datas), nulls,
                convert=convert)
    # mixed / ragged: fall back to object list
    elems: List[Elem] = []
    for p in parts:
        elems.extend(p.elements())
    return ColumnBatch(rows, elems, nulls)
