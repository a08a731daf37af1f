"""Exact-frame decode planning and execution.

Capability parity: reference DecoderAutomata (decoder_automata.h:28-88,
decoder_automata.cpp:72-238) — turn "give me display frames {i...}" into
minimal keyframe-aligned packet feeds, decode them, and deliver exactly the
requested frames.

Instead of the reference's two-thread feeder/retriever state machine, the
whole run executes inside one C call (scvid_decode_run) with a wanted-frame
mask; parallelism comes from running many automata on separate Python threads
(the C side releases the GIL).
"""

from __future__ import annotations

import threading
from dataclasses import dataclass
from typing import List, Optional, Sequence, Tuple

import numpy as np

from ..common import ScannerException
from ..storage.backend import StorageBackend
from ..storage.metadata import VideoDescriptor
from .lib import Decoder


@dataclass
class DecodeRun:
    """One keyframe-aligned packet feed."""
    start_dec: int       # first packet (decode order), always a keyframe
    end_dec: int         # last packet fed, inclusive
    out_disp: np.ndarray  # display indices delivered, ascending


class VideoIndex:
    """Derived lookup structures over a VideoDescriptor's sample index."""

    def __init__(self, vd: VideoDescriptor):
        self.vd = vd
        n = vd.num_frames
        pts = np.asarray(vd.sample_pts)
        # decode indices sorted by presentation time = display order
        self.dec_of_disp = np.argsort(pts, kind="stable").astype(np.int64)
        self.disp_of_dec = np.empty(n, np.int64)
        self.disp_of_dec[self.dec_of_disp] = np.arange(n)
        # feeding packets [0..M[d]] guarantees display frames [0..d] emitted
        self.max_dec_through_disp = np.maximum.accumulate(self.dec_of_disp)
        self.kf_decs = np.asarray(vd.keyframe_indices)
        self.kf_disps = self.disp_of_dec[self.kf_decs]
        if not np.all(np.diff(self.kf_disps) > 0):
            # sort keyframes by display position (defensive; decode order
            # keyframes are display-ordered for closed-GOP streams)
            order = np.argsort(self.kf_disps)
            self.kf_decs = self.kf_decs[order]
            self.kf_disps = self.kf_disps[order]

    def governing_keyframe(self, disp: int) -> Tuple[int, int]:
        """(keyframe decode idx, keyframe display idx) for a display frame."""
        i = int(np.searchsorted(self.kf_disps, disp, side="right")) - 1
        if i < 0:
            raise ScannerException(f"no keyframe before display frame {disp}")
        return int(self.kf_decs[i]), int(self.kf_disps[i])

    def plan(self, wanted_disp: Sequence[int],
             decode_through: int = 16) -> List[DecodeRun]:
        """Build minimal decode runs covering `wanted_disp` (sorted unique).

        decode_through: if the next wanted frame's keyframe starts within
        this many packets of the current run's end, keep decoding through
        rather than reseeking — a reseek costs a codec flush and re-reads.
        """
        wanted = np.unique(np.asarray(list(wanted_disp), dtype=np.int64))
        if len(wanted) == 0:
            return []
        if wanted[0] < 0 or wanted[-1] >= self.vd.num_frames:
            raise ScannerException(
                f"frame request {wanted[0]}..{wanted[-1]} out of range "
                f"(video has {self.vd.num_frames} frames)")
        runs: List[DecodeRun] = []
        cur_start = cur_end = -1
        cur_disps: List[int] = []

        def close_run():
            if cur_start < 0:
                return
            runs.append(DecodeRun(cur_start, cur_end,
                                  np.asarray(cur_disps, np.int64)))

        for w in wanted:
            kf_dec, kf_disp = self.governing_keyframe(int(w))
            need_end = int(self.max_dec_through_disp[w])
            if cur_start >= 0 and kf_dec <= cur_end + decode_through:
                cur_end = max(cur_end, need_end)
                cur_disps.append(int(w))
            else:
                close_run()
                cur_start, cur_end = kf_dec, need_end
                cur_disps = [int(w)]
        close_run()
        return runs


class DecoderAutomata:
    """Owns one Decoder handle and executes decode plans against stored
    packet data."""

    def __init__(self, backend: StorageBackend, vd: VideoDescriptor,
                 data_path: str, n_threads: int = 1,
                 output_format: str = "rgb24"):
        self.backend = backend
        self.vd = vd
        self.index = VideoIndex(vd)
        # in-place ingested streams read from the original container file
        self.data_path = vd.data_path or data_path
        self._external = bool(vd.data_path)
        # "rgb24": (n, h, w, 3) frames; "yuv420": (n, frame_bytes) planar
        # I420 rows at 1.5 B/px for device-side conversion
        # (kernels/color.py) — half the host->device bytes
        self.output_format = output_format
        self.decoder = Decoder(vd.codec, vd.extradata, vd.width, vd.height,
                               n_threads, output_format=output_format)
        # get_frames' scratch for a request that spans several runs or
        # repeats rows (grown geometrically, reused across calls; the
        # reference pools buffers for the same reason, util/memory.cpp
        # BlockAllocator).  Streaming decodes (StreamSession) and the
        # one-run fast path write where the caller says and use none.
        self._scratch = np.empty(0, np.uint8)

    @property
    def frame_bytes(self) -> int:
        from .lib import yuv420_frame_bytes
        if self.output_format == "yuv420":
            return yuv420_frame_bytes(self.vd.height, self.vd.width)
        return self.vd.height * self.vd.width * 3

    @property
    def frame_shape(self) -> Tuple[int, ...]:
        """Shape of one delivered frame: (h, w, 3) for "rgb24", the
        planar row (frame_bytes,) for "yuv420"."""
        if self.output_format == "rgb24":
            return (self.vd.height, self.vd.width, 3)
        return (self.frame_bytes,)

    @property
    def codec_frames(self) -> int:
        """Frames the codec has decoded for this automaton so far: the
        ones delivered and the ones a run decoded only to reach them
        (from the keyframe up, and through to the next wanted frame)."""
        return self.decoder.codec_frames

    def _scratch_buf(self, nbytes: int) -> np.ndarray:
        if self._scratch.nbytes < nbytes:
            self._scratch = np.empty(int(nbytes * 1.5) + 1, np.uint8)
        return self._scratch

    def close(self):
        self.decoder.close()
        self._scratch = np.empty(0, np.uint8)

    def _read_packets(self, start_dec: int, end_dec: int
                      ) -> Tuple[bytes, np.ndarray]:
        offs = self.vd.sample_offsets[start_dec:end_dec + 1].astype(np.int64)
        sizes = self.vd.sample_sizes[start_dec:end_dec + 1].astype(np.int64)
        if self._external:
            # external container: samples may be non-contiguous; one spanning
            # read then slice (containers interleave audio but video spans
            # are still compact enough)
            lo = int(offs.min())
            hi = int((offs + sizes).max())
            with open(self.data_path, "rb") as f:
                f.seek(lo)
                span = f.read(hi - lo)
            parts = [span[o - lo:o - lo + s] for o, s in zip(offs, sizes)]
            return b"".join(parts), sizes.astype(np.uint64)
        # packed stream: contiguous by construction
        lo = int(offs[0])
        hi = int(offs[-1] + sizes[-1])
        data = self.backend.read_range(self.data_path, lo, hi - lo)
        if len(data) != hi - lo:
            raise ScannerException(
                f"short packet read from {self.data_path}")
        return data, sizes.astype(np.uint64)

    def _decode_run_pts(self, run: DecodeRun, out: np.ndarray) -> None:
        """Decode one run into `out` ((n_out, h*w*3) rows in display
        order), selecting frames by TIMESTAMP rather than emission
        position.  Pts matching keeps delivery exact on streams where
        positional masks break: open-GOP seeks (the decoder emits or
        drops leading frames whose references precede the keyframe) and
        VFR containers (display order is defined by pts alone).  If a
        wanted frame is not delivered — an open-GOP leading frame whose
        references live in the previous GOP — the whole run retries from
        one keyframe earlier until it decodes or the stream start is hit
        (reference decoder_automata feeder restarts at decoder_automata
        .cpp:238; the reference never handled open GOPs at all)."""
        h, w = self.vd.height, self.vd.width
        pts_all = np.asarray(self.vd.sample_pts, np.int64)
        wanted_pts = pts_all[self.index.dec_of_disp[
            np.asarray(run.out_disp, np.int64)]]
        start = run.start_dec
        while True:
            data, sizes = self._read_packets(start, run.end_dec)
            pkt_pts = pts_all[start:run.end_dec + 1]
            self.decoder.reset()
            n, oh, ow, deliv = self.decoder.decode_run_pts(
                data, sizes, pkt_pts, wanted_pts, out, flush=True)
            if n and (oh, ow) != (h, w):
                raise ScannerException(
                    f"decoded geometry {oh}x{ow} != descriptor {h}x{w}")
            if deliv.all():
                return
            # open-GOP leading frames: restart from one keyframe earlier
            ki = int(np.searchsorted(self.index.kf_decs, start,
                                     side="right")) - 1
            if ki <= 0 or start <= 0:
                missing = wanted_pts[~deliv].tolist()
                raise ScannerException(
                    f"frames with pts {missing[:5]} not delivered "
                    f"(run {start}..{run.end_dec}; stream damaged or "
                    f"index stale)")
            start = int(self.index.kf_decs[ki - 1])

    def stream_frames(self, rows: Sequence[int], packets_per_call: int = 16,
                      max_frames_per_yield: int = 16):
        """Incrementally decode ascending unique display rows, yielding
        ``(row_array, frames_array)`` slices of at most
        ``max_frames_per_yield`` frames: a StreamSession driven into a
        fresh array a yield, so every yield owns its memory.  Yields are
        disjoint and cover exactly `rows`; peak memory is one yield."""
        session = StreamSession(self, rows, packets_per_call)
        while session.remaining:
            out = np.empty(
                (min(max_frames_per_yield, session.remaining),)
                + self.frame_shape, np.uint8)
            yield session.decode_into(out), out

    def get_frames(self, rows: Sequence[int]) -> np.ndarray:
        """Decode exactly the given display-order frame indices.

        Returns uint8 array in *request order* — duplicates and arbitrary
        order allowed (Gather semantics).  Shape is
        (len(rows), h, w, 3) for "rgb24" output, or
        (len(rows), frame_bytes) planar I420 rows for "yuv420".
        """
        rows_arr = np.asarray(list(rows), np.int64)
        frame_bytes = self.frame_bytes
        shape = (len(rows_arr),) + self.frame_shape
        if len(rows_arr) == 0:
            return np.zeros(shape, np.uint8)
        runs = self.index.plan(rows_arr)
        result = np.empty(shape, np.uint8)
        if len(runs) == 1 and np.array_equal(
                np.asarray(runs[0].out_disp, np.int64), rows_arr):
            # fast path: the run emits exactly the requested rows in
            # request order — decode straight into the result batch (the
            # zero-copy head of the engine's batched column path)
            self._decode_run_pts(runs[0], result.reshape(-1))
            return result
        # request-order positions of each decoded display index
        positions: dict = {}
        for i, r in enumerate(rows_arr.tolist()):
            positions.setdefault(int(r), []).append(i)
        for run in runs:
            n_out = len(run.out_disp)
            scratch = self._scratch_buf(n_out * frame_bytes)
            out = scratch[:n_out * frame_bytes]
            self._decode_run_pts(run, out)
            out = out.reshape((n_out,) + shape[1:])
            for i, d in enumerate(run.out_disp):
                for pos in positions.get(int(d), ()):
                    result[pos] = out[i]
        return result


class StreamSession:
    """One incremental decode of ascending unique display rows of one
    automaton, driven by the caller's destination: each
    ``decode_into(out)`` writes the next ``len(out)`` rows' frames where
    the caller will use them.  This is the work-packet streaming
    loader's decode primitive (reference element cache + feeder threads,
    evaluate_worker.h:207-218 / decoder_automata.cpp).

    One codec session per keyframe run: packets are fed in slices of
    ``packets_per_call`` through repeated bounded
    ``decode_run_pts_stream`` calls WITHOUT resetting the codec (the C
    layer stops — does not error — once the slice is full, reports the
    packets it consumed and keeps its backlog for the next call), so
    the destination is a work packet, not a packet run plus a
    reorder-delay margin.  Frames arrive in display order.  Open-GOP /
    false-keyframe retries restart the run from an earlier keyframe for
    the still-undelivered rows only, so those rows come after later
    ones: the rows returned say what each slot holds.
    """

    _NO_SIZES = np.zeros(0, np.uint64)
    _NO_PTS = np.zeros(0, np.int64)

    def __init__(self, auto: DecoderAutomata, rows: Sequence[int],
                 packets_per_call: int = 16):
        rows_arr = np.unique(np.asarray(list(rows), np.int64))
        self._auto = auto
        self._packets_per_call = packets_per_call
        self._pts_all = np.asarray(auto.vd.sample_pts, np.int64)
        self._runs = iter(auto.index.plan(rows_arr))
        # rows no decode_into has delivered yet, over all runs
        self.remaining = len(rows_arr)
        # the open run: its undelivered rows and their timestamps, its
        # last packet, the keyframe packet this attempt started from
        # and the next packet to feed
        self._rows = self._pts = self._NO_PTS
        self._end_dec = self._start = self._pos = -1

    def _open(self, start: int) -> None:
        """(Re)start the open run's decode at keyframe packet `start`."""
        self._auto.decoder.reset()
        self._start = self._pos = start

    def decode_into(self, out: np.ndarray) -> np.ndarray:
        """Decode the next ``min(len(out), remaining)`` rows into `out`
        (uint8, C-contiguous, ``(k,) + frame_shape`` or any shape of
        ``frame_bytes`` a row); nothing past them is written.  Returns
        their rows: ``out[i]`` holds row ``result[i]``, ascending but for
        rows an open-GOP retry delivered late."""
        auto = self._auto
        frame_bytes = auto.frame_bytes
        if out.dtype != np.uint8 or not out.flags["C_CONTIGUOUS"] \
                or out.nbytes != len(out) * frame_bytes:
            raise ScannerException(
                f"decode destination {out.dtype}{out.shape} is not "
                f"C-contiguous uint8 rows of {frame_bytes} bytes")
        if len(out) and not self.remaining:
            raise ScannerException(
                "decode asked for rows past the end of its session")
        k = min(len(out), self.remaining)
        flat = out.reshape(-1)
        got: List[np.ndarray] = []
        done = 0
        while done < k:
            if not len(self._rows):
                run = next(self._runs)
                self._rows = np.asarray(run.out_disp, np.int64)
                self._pts = self._pts_all[auto.index.dec_of_disp[self._rows]]
                self._end_dec = run.end_dec
                self._open(run.start_dec)
            if self._pos <= self._end_dec:
                end = min(self._pos + self._packets_per_call - 1,
                          self._end_dec)
                data, sizes = auto._read_packets(self._pos, end)
                pkt_pts = self._pts_all[self._pos:end + 1]
            else:
                # flush-only continuation: harvest codec backlog
                data, sizes, pkt_pts = b"", self._NO_SIZES, self._NO_PTS
                end = self._pos - 1
            n, oh, ow, deliv, consumed = \
                auto.decoder.decode_run_pts_stream(
                    data, sizes, pkt_pts, self._pts,
                    flat[done * frame_bytes:k * frame_bytes],
                    max_frames=k - done, flush=(end >= self._end_dec))
            if n and (oh, ow) != (auto.vd.height, auto.vd.width):
                raise ScannerException(
                    f"decoded geometry {oh}x{ow} != descriptor "
                    f"{auto.vd.height}x{auto.vd.width}")
            if n:
                got.append(self._rows[deliv])
                done += n
                self.remaining -= n
            self._rows = self._rows[~deliv]
            self._pts = self._pts[~deliv]
            self._pos += consumed
            if self._pos > self._end_dec and n == 0 and consumed == 0:
                # flushed dry with rows undelivered: leading open-GOP
                # frames (or a false keyframe); retry them from one
                # keyframe earlier
                ki = int(np.searchsorted(auto.index.kf_decs, self._start,
                                         side="right")) - 1
                if ki <= 0 or self._start <= 0:
                    raise ScannerException(
                        f"frames with pts {self._pts[:5].tolist()} not "
                        f"delivered (run {self._start}..{self._end_dec}; "
                        f"stream damaged or index stale)")
                self._open(int(auto.index.kf_decs[ki - 1]))
        return np.concatenate(got) if got else self._NO_PTS
