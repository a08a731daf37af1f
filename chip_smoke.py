"""chip_smoke.py — the quickest proof that scanner_tpu still starts on the chip.

Drives the README quick-start path (Client, ingest_videos, sc.io.Input ->
sc.ops.* -> sc.io.Output, sc.run, NamedStream.load) once, in ONE process,
on the TPU JAX selects, and checks what comes out against the repo's own
references.  It refuses to run on any other backend, starts no other
Python process, and catches nothing: the first failed check or raised
exception ends the run with a non-zero exit code and no result line.

On success the last line of stdout is
    {"ok": true, "device": {"platform": "tpu", "kind": "...", "count": N}}

Phase wall seconds are printed as they finish.  Build, synth, ingest and
each graph's first (compile + warm-up) run are SET-UP; the repeated
Histogram pass is smoke timing, not a benchmark.

Run it from the repo root:  python chip_smoke.py
"""

import contextlib
import importlib.metadata
import json
import logging
import os
import shutil
import subprocess
import sys
import tempfile
import threading
import time

ROOT = os.path.dirname(os.path.abspath(__file__))

# the smoke clip: 600 frames of 640x480, keyint 32
N_FRAMES, W, H, KEYINT = 600, 640, 480, 32
CHAIN_ROWS = 96      # Resize -> Blur -> Histogram -> HistDiff golden chain
POSE_ROWS = 128      # PoseDetect at its default width
KERNEL_FRAMES = 96   # histogram_frames direct check: 96x480x640x3 uint8
# PoseDetect computes in bfloat16 (unit roundoff 2**-8) through ~12
# layers; scores on the chip may differ from the CPU backend's by that
# much relative to the largest score, not more
POSE_RTOL = 12 * 2.0 ** -8
# flash kernel vs the float32 reference at highest matmul precision: the
# kernel's f32 MXU operands may be rounded to bf16 per pass
FLASH_ATOL = 2e-2
# pipeline pixels vs sc.load_frames (swscale): the two YUV->RGB
# conversions differ by at most this many levels (kernels/color.py) ...
SWS_PIXEL_TOL = 4
# ... so a histogram sample changes its 16-level bin only when it sits
# within SWS_PIXEL_TOL of a bin edge: 2*4/16 of uniformly spread pixels
# at the very most.  The seeded clip measured 0.13 mean / 0.17 max on
# the chip (PERF.md); half the geometric ceiling leaves room and still
# fails a conversion that is off by a bin
SWS_MOVED_MAX = 0.25

PHASES = []  # (name, seconds, is_setup)


def say(msg):
    print(f"[smoke] {msg}", flush=True)


def check(cond, msg):
    if not cond:
        raise SystemExit(f"[smoke] FAILED: {msg}")
    say(f"ok: {msg}")


@contextlib.contextmanager
def phase(name, setup=False):
    t0 = time.time()
    try:
        yield
    finally:
        dt = time.time() - t0
        PHASES.append((name, dt, setup))
        say(f"phase {name}: {dt:.2f} s" + (" (set-up)" if setup else ""))


class WarningCounter(logging.Handler):
    """Every WARNING-or-above record from any scanner_tpu logger (the
    package's own stderr handler prints them): a warm-up that failed, a
    frame-cache page build that 'carried on', a chain that fell back to
    a per-instance jit, a health alert that fired, a remediation the
    controller applied.  One record fails the smoke."""

    def __init__(self):
        super().__init__(level=logging.WARNING)
        self.records = []

    def emit(self, record):
        self.records.append(record)


class LiveKernels:
    """Samples the engine's live evaluators while a graph runs and
    keeps, per pipeline instance, what `probe` read off that run's own
    kernel instance of `op` — evaluators close with the run, so this is
    the only moment to look at them."""

    def __init__(self, live_evaluators, op, probe):
        self._live, self._op, self._probe = live_evaluators, op, probe
        self.seen = {}
        self._stop = threading.Event()
        self._thread = threading.Thread(target=self._watch, daemon=True)

    def _watch(self):
        while not self._stop.is_set():
            for te in self._live():
                for ki in list(te.kernels.values()):
                    if ki.node.name == self._op:
                        self.seen[te.instance] = self._probe(te, ki.kernel)
            self._stop.wait(0.02)

    def __enter__(self):
        self._thread.start()
        return self

    def __exit__(self, *exc):
        self._stop.set()
        self._thread.join()


def metric_total(snap, series):
    return sum(s["value"] for s in snap.get(series, {}).get("samples", []))


def metric_by(snap, series, label):
    out = {}
    for s in snap.get(series, {}).get("samples", []):
        k = s["labels"].get(label, "_")
        out[k] = out.get(k, 0.0) + s["value"]
    return out


def main():
    # -- 0. the chip, or nothing ------------------------------------------
    import jax
    backend = jax.default_backend()
    if backend != "tpu":
        raise SystemExit(
            f"[smoke] FAILED: needs the TPU backend, JAX found "
            f"'{backend}' ({[str(d) for d in jax.devices()]}); this "
            f"script measures nothing on any other platform")
    dev0 = jax.devices()[0]
    device = {"platform": dev0.platform, "kind": dev0.device_kind,
              "count": len(jax.devices())}

    # -- 1. the native video layer, built here from cpp/ at HEAD ----------
    # the .so is git-ignored and a copied tree does not preserve the
    # mtimes video/lib.py's staleness check trusts: always rebuild,
    # before the first scanner_tpu.video import
    t_build = time.time()
    with phase("build libscvid", setup=True):
        subprocess.run(["make", "-B", "-C", os.path.join(ROOT, "cpp")],
                       check=True, stdout=subprocess.DEVNULL)
    so = os.path.join(ROOT, "scanner_tpu", "video", "libscvid.so")
    check(os.path.getmtime(so) >= t_build - 1,
          f"libscvid.so built by this run from cpp/ ({so})")

    smoke(device)
    print(json.dumps({"ok": True, "device": device}), flush=True)


def smoke(device):
    import jax
    import jax.numpy as jnp
    import numpy as np

    import scanner_tpu.kernels  # noqa: F401 — registers the stdlib ops
    import scanner_tpu.models  # noqa: F401 — registers the model ops
    from scanner_tpu import (CacheMode, Client, NamedStream,
                             NamedVideoStream, PerfParams)
    from scanner_tpu import video as scv
    from scanner_tpu.engine import evaluate as ev
    from scanner_tpu.graph import fusion
    from scanner_tpu.kernels import pallas_attention, pallas_ops
    from scanner_tpu.kernels.color import (yuv420_to_rgb_device,
                                           yuv420_to_rgb_host)
    from scanner_tpu.kernels.imgproc import _histogram_cmp_impl
    from scanner_tpu.models.pose import heatmaps_to_keypoints
    from scanner_tpu.parallel.ring_attention import reference_attention
    from scanner_tpu.storage import Database, make_storage
    from scanner_tpu.util import coststats
    from scanner_tpu.util import metrics as mx
    from scanner_tpu.util.memstats import device_label

    warnings = WarningCounter()
    logging.getLogger("scanner_tpu").addHandler(warnings)

    root = tempfile.mkdtemp(prefix="scsmoke_")
    try:
        db_path = os.path.join(root, "db")
        sc = Client(db_path=db_path)
        # Client() applied the cache rule: JAX's own reading of
        # JAX_COMPILATION_CACHE_DIR, else the fixed in-checkout path
        cache_dir = jax.config.jax_compilation_cache_dir

        def n_cached():
            return len(os.listdir(cache_dir)) \
                if os.path.isdir(cache_dir) else 0

        say(f"platform={device['platform']} device_kind={device['kind']!r} "
            f"devices={device['count']} "
            f"local_devices={len(jax.local_devices())}")
        say(f"jax={jax.__version__} "
            f"jaxlib={importlib.metadata.version('jaxlib')} "
            f"libtpu={importlib.metadata.version('libtpu')} "
            f"python={sys.version.split()[0]} cpus={os.cpu_count()}")
        say(f"compile cache dir in effect: {cache_dir} "
            f"(JAX_COMPILATION_CACHE_DIR "
            f"{'set' if os.environ.get('JAX_COMPILATION_CACHE_DIR') else 'unset'}"
            f", {n_cached()} entries at start)")

        # -- 2. synthesize + ingest the bench-sized clip ------------------
        clip = os.path.join(root, "smoke.mp4")
        with phase("synthesize clip", setup=True):
            scv.synthesize_video(clip, num_frames=N_FRAMES, width=W,
                                 height=H, fps=30, keyint=KEYINT)
        with phase("ingest", setup=True):
            _, failed = sc.ingest_videos([("smoke", clip)])
        check(not failed, f"ingest of {N_FRAMES} frames {W}x{H}")

        def frames_col(rows=None):
            col = sc.io.Input([NamedVideoStream(sc, "smoke")])
            return col if rows is None \
                else sc.streams.Range(col, [(0, rows)])

        def run(node, name):
            out = NamedStream(sc, name)
            sc.run(sc.io.Output(node, [out]), PerfParams.estimate(),
                   cache_mode=CacheMode.Overwrite, show_progress=False)
            return list(out.load())

        # -- 3a. Histogram over all rows: staged path, Pallas kernel ------
        with phase("Histogram 600 rows, first run (compile + warm-up)",
                   setup=True), \
                LiveKernels(ev.live_evaluators, "Histogram",
                            lambda te, k: k._on_tpu) as hist_live:
            hist_rows = run(sc.ops.Histogram(frame=frames_col()),
                            "smoke_hist")
        check(len(hist_rows) == N_FRAMES, f"{N_FRAMES} Histogram rows")
        got = np.stack([np.asarray(r) for r in hist_rows])
        check(got.shape == (N_FRAMES, 3, 16) and got.dtype == np.int32,
              f"Histogram output shape {got.shape} dtype {got.dtype}")
        check(hist_live.seen and all(hist_live.seen.values()),
              f"the run's own Histogram kernel instances took the Pallas "
              f"path (instance: _on_tpu = {hist_live.seen})")

        def bincount_rows(frames):
            return np.stack([
                np.stack([np.bincount(f[..., c].ravel() >> 4, minlength=16)
                          for c in range(3)]) for f in frames])

        # DEVIATION from "np.bincount over sc.load_frames", stated: on an
        # accelerator the engine ships decoded frames as YUV420 and
        # converts ON DEVICE (kernels/color.py: BT.601 fixed point,
        # nearest chroma), while sc.load_frames decodes through swscale
        # (bilinear chroma).  The two differ by a few levels by design,
        # so no pipeline output can be integer-equal to a swscale
        # histogram.  The chain of checks instead:
        #   (a) the chip's conversion of the clip's own wire frames is
        #       bit-equal to the host flavor of the same arithmetic;
        #   (b) every Histogram row is integer-exact against np.bincount
        #       over the host flavor (the package's own code, but run on
        #       the host — it shares no device path with the engine);
        #   (c) the INDEPENDENT reference, sc.load_frames, bounds both:
        #       pixels within SWS_PIXEL_TOL levels, and at most
        #       SWS_MOVED_MAX of a row's histogram samples in another
        #       bin than np.bincount over sc.load_frames puts them.
        say("DEVIATION: the integer-exact Histogram reference is "
            "np.bincount over the host flavor of the engine's YUV420 "
            "wire conversion, not over sc.load_frames (swscale); "
            "sc.load_frames bounds it below")
        ref_db = Database(make_storage("posix", db_path=db_path))

        def wire_rows(rows):
            """The flat I420 rows the engine ships for `rows`."""
            auto = scv.open_automata(ref_db, "smoke",
                                     output_format="yuv420")
            try:
                return np.asarray(auto.get_frames(list(rows)))
            finally:
                auto.close()

        def wire_frames(rows):
            return yuv420_to_rgb_host(wire_rows(rows), H, W)

        n_sw = 64
        with phase("on-chip YUV420->RGB vs the host flavor", setup=True):
            flat = wire_rows(range(n_sw))
            on_chip = np.asarray(yuv420_to_rgb_device(
                jax.device_put(flat), H, W))
            on_host = yuv420_to_rgb_host(flat, H, W)
        check(on_chip.shape == (n_sw, H, W, 3)
              and np.array_equal(on_chip, on_host),
              f"(a) yuv420_to_rgb on the chip bit-equal to the host "
              f"flavor over rows 0..{n_sw - 1} of the clip")
        with phase("Histogram reference (host decode + np.bincount)",
                   setup=True):
            ref_wire = np.concatenate([
                bincount_rows(wire_frames(
                    range(s, min(s + 100, N_FRAMES))))
                for s in range(0, N_FRAMES, 100)])
        check(np.array_equal(got, ref_wire),
              "(b) every Histogram row integer-exact vs np.bincount over "
              "the host flavor of the YUV420 wire")
        sw = sc.load_frames("smoke", range(n_sw))
        pix = np.abs(on_chip.astype(np.int16) - sw.astype(np.int16))
        moved = np.abs(got[:n_sw].astype(np.int64) - bincount_rows(sw)) \
            .sum(axis=(1, 2)) / (2.0 * 3 * W * H)
        say(f"chip-converted frames vs sc.load_frames (swscale), rows "
            f"0..{n_sw - 1}: |pixel diff| mean {pix.mean():.3f} max "
            f"{pix.max()}; share of the engine's histogram samples in "
            f"another bin: mean {moved.mean():.4f} max {moved.max():.4f}")
        check(pix.max() <= SWS_PIXEL_TOL,
              f"(c) chip-converted pixels within {SWS_PIXEL_TOL} levels "
              f"of sc.load_frames")
        check(moved.max() <= SWS_MOVED_MAX,
              f"(c) engine Histogram rows within {SWS_MOVED_MAX} of "
              f"np.bincount over sc.load_frames (share of samples in "
              f"another bin, worst row)")

        # -- 3b. the golden chain, fused (default) vs staged --------------
        chain_id = "Resize+Blur+Histogram"

        def chain_graph():
            col = sc.ops.Resize(frame=frames_col(CHAIN_ROWS),
                                width=[W // 2], height=[H // 2])
            col = sc.ops.Blur(frame=col, kernel_size=3, sigma=1.1)
            col = sc.ops.Histogram(frame=col)
            return sc.ops.HistDiff(frame=col)

        check(fusion.enabled(), "whole-chain fusion is on by default")
        with phase("golden chain fused, first run (compile + warm-up)",
                   setup=True):
            fused = run(chain_graph(), "smoke_chain_fused")
        snap = mx.registry().snapshot()
        check(metric_by(snap, "scanner_tpu_op_rows_total", "op")
              .get(chain_id, 0) >= CHAIN_ROWS,
              f"chain {chain_id} ran as ONE fused program "
              f"({CHAIN_ROWS} rows under its chain id)")
        fusion.set_enabled(False)
        try:
            with phase("golden chain staged, first run (compile + "
                       "warm-up)", setup=True):
                staged = run(chain_graph(), "smoke_chain_staged")
        finally:
            fusion.set_enabled(True)
        check(len(fused) == len(staged) == CHAIN_ROWS,
              f"{CHAIN_ROWS} rows from both chain runs")
        check(all(np.array_equal(np.asarray(a), np.asarray(b))
                  for a, b in zip(fused, staged)),
              "fused chain rows bit-equal to the staged run of the same "
              "graph")
        check(all(np.isfinite(float(a)) for a in fused),
              "chain outputs finite")

        # -- 3c. PoseDetect at its default width (32), seeded weights -----
        def pose_probe(te, kern):
            own = te.device or jax.local_devices()[0]
            on = {d for leaf in jax.tree_util.tree_leaves(kern.params)
                  for d in leaf.devices()}
            return (device_label(own),
                    sorted(device_label(d) for d in on), kern)

        with phase("PoseDetect width 32, 128 rows, first run (compile + "
                   "warm-up)", setup=True), \
                LiveKernels(ev.live_evaluators, "PoseDetect",
                            pose_probe) as pose_live:
            pose_rows = run(sc.ops.PoseDetect(frame=frames_col(POSE_ROWS)),
                            "smoke_pose")
        pose = np.stack([np.asarray(r) for r in pose_rows])
        check(pose.shape == (POSE_ROWS, 17, 3),
              f"PoseDetect output shape {pose.shape}")
        check(bool(np.isfinite(pose).all()), "PoseDetect outputs finite")

        # the run's own kernel instances, one per pipeline instance:
        # each keeps its weights on the chip it owns
        n_inst = ev.default_pipeline_instances(None)
        check(sorted(pose_live.seen) == list(range(n_inst)),
              f"saw the PoseDetect kernel of every pipeline instance "
              f"({sorted(pose_live.seen)} of {n_inst})")
        for i, (own, on, _kern) in sorted(pose_live.seen.items()):
            check(on == [own],
                  f"PoseDetect instance {i}/{n_inst}: params resident on "
                  f"its own device {own} (found {on})")
        check(len({own for own, _on, _k in pose_live.seen.values()})
              == n_inst, "every PoseDetect instance owns a different chip")
        with phase("PoseDetect CPU-backend reference, first batch",
                   setup=True):
            cpu = jax.devices("cpu")[0]
            kern = pose_live.seen[0][2]
            nb = 8  # the op's declared batch
            # the engine fed the model the device-converted YUV wire;
            # feed the reference the same pixels
            first = wire_frames(range(nb))
            params_cpu = jax.device_put(
                jax.tree_util.tree_map(np.asarray, kern.params), cpu)
            heat = jax.jit(kern.model.apply)(
                params_cpu, jax.device_put(first[:, None], cpu))
            heat = np.asarray(heat.astype(jnp.float32))[:, 0]
            ref_kp = np.stack([heatmaps_to_keypoints(h) for h in heat])
        scale = float(np.abs(ref_kp[..., 2]).max())
        err = float(np.abs(pose[:nb, :, 2] - ref_kp[..., 2]).max())
        say(f"PoseDetect first-batch scores: max |chip - cpu| = {err:.5f}, "
            f"largest |score| = {scale:.5f}, bound {POSE_RTOL * scale:.5f}")
        check(err <= POSE_RTOL * scale,
              "PoseDetect first-batch scores within bf16 tolerance of "
              "the same params on jax.devices('cpu')[0]")

        # -- 4. both Pallas kernels, compiled natively --------------------
        rng = np.random.default_rng(0)
        with phase("pallas histogram_frames native compile + check",
                   setup=True):
            x = jax.device_put(rng.integers(
                0, 256, (KERNEL_FRAMES, H, W, 3), dtype=np.uint8))
            ph = np.asarray(pallas_ops.histogram_frames(x, interpret=False))
            xh = np.asarray(_histogram_cmp_impl(x))
        check(ph.shape == (KERNEL_FRAMES, 3, 16)
              and np.array_equal(ph, xh),
              f"pallas histogram_frames (interpret=False) at "
              f"{x.shape} equals _histogram_cmp_impl")
        check(np.array_equal(ph[:2], bincount_rows(np.asarray(x[:2]))),
              "pallas histogram_frames equals np.bincount")
        del x

        BH, T, D = 8, 1024, 64
        q4, k4, v4 = (jnp.asarray(rng.standard_normal((1, T, BH, D)),
                                  jnp.float32) for _ in range(3))

        def heads(a):  # (1, T, BH, D) -> (BH, T, D)
            return jnp.transpose(a[0], (1, 0, 2))

        for causal in (False, True):
            with phase(f"pallas flash_block_update native compile + check "
                       f"(causal={causal})", setup=True):
                m, l, acc = pallas_attention.flash_block_update(
                    heads(q4) * D ** -0.5, heads(k4), heads(v4),
                    jnp.full((BH, T), pallas_attention.NEG_INF,
                             jnp.float32),
                    jnp.zeros((BH, T), jnp.float32),
                    jnp.zeros((BH, T, D), jnp.float32),
                    0, 0, causal=causal, interpret=False)
                out = np.asarray(acc / l[..., None])
                with jax.default_matmul_precision("highest"):
                    ref = np.asarray(heads(reference_attention(
                        q4, k4, v4, causal=causal)))
            ferr = float(np.abs(out - ref).max())
            say(f"flash_block_update causal={causal}: max |kernel - "
                f"reference| = {ferr:.2e}")
            check(bool(np.isfinite(out).all()) and ferr <= FLASH_ATOL,
                  f"pallas flash_block_update (interpret=False, 256x256 "
                  f"tiles, BH={BH} T={T} D={D}, causal={causal}) matches "
                  f"reference_attention within {FLASH_ATOL}")

        # -- 5. the device path was the one taken -------------------------
        snap = mx.registry().snapshot()
        h2d = metric_total(snap, "scanner_tpu_h2d_bytes_total")
        check(h2d > 0, f"scanner_tpu_h2d_bytes_total = {int(h2d)} > 0")
        local = jax.local_devices()
        want = ["default"] if len(local) == 1 \
            else [device_label(d) for d in local]
        tasks = metric_by(snap, "scanner_tpu_device_tasks_total", "device")
        say(f"scanner_tpu_device_tasks_total by device: {tasks}")
        check(all(tasks.get(d, 0) > 0 for d in want),
              f"every local device evaluated tasks ({want})")
        fc = {k: metric_total(snap, f"scanner_tpu_framecache_{k}_total")
              for k in ("hits", "misses", "inserts")}
        say(f"frame cache: {fc}")
        check(fc["misses"] > 0 and fc["inserts"] > 0,
              "frame-cache miss/insert counters moved")
        if len(local) == 1:
            # with several chips a clip's pages live on whichever chip
            # ran the earlier task, so a re-read need not hit
            check(fc["hits"] > 0, "frame-cache hit counter moved")

        # -- 6. the repeated pass: nothing left to compile ----------------
        before = coststats.ledger_summary()
        with phase("Histogram 600 rows, repeated pass (smoke timing, not "
                   "a benchmark)"):
            again = run(sc.ops.Histogram(frame=frames_col()),
                        "smoke_hist_again")
        after = coststats.ledger_summary()
        check(np.array_equal(
            np.stack([np.asarray(r) for r in again]), got),
            "repeated Histogram pass reproduces the first")
        check(after["compiles"] == before["compiles"],
              f"zero new compiles on the repeated pass "
              f"({after['compiles']} observed in all)")
        say(f"compile ledger: {after['compiles']} compiles, "
            f"{after['compile_seconds']} s, by persistent-cache outcome "
            f"{after['by_cache']}, hit rate {after['cache_hit_rate']}")
        check(n_cached() > 0,
              f"compile cache directory holds {n_cached()} entries")

        snap = mx.registry().snapshot()
        fired = {}
        for smp in snap.get("scanner_tpu_alerts_transitions_total",
                            {}).get("samples", []):
            if smp["labels"].get("state") == "firing" and smp["value"]:
                fired[smp["labels"]["rule"]] = int(smp["value"])
        say(f"health alerts fired (rule: times): {fired}")
        say("evaluate-stage busy seconds by device: "
            f"{metric_by(snap, 'scanner_tpu_evaluate_open_seconds_total', 'device')}"
            f"; stage seconds: "
            f"{metric_by(snap, 'scanner_tpu_stage_seconds_total', 'stage')}"
            f"; evaluators warming now: "
            f"{metric_total(snap, 'scanner_tpu_evaluator_warming')}")
        check(not warnings.records,
              f"zero WARNING-or-above records from scanner_tpu loggers "
              f"(saw {[(r.name, r.getMessage()[:160]) for r in warnings.records]})")
        for d in jax.local_devices():
            say(f"{device_label(d)} peak_bytes_in_use = "
                f"{d.memory_stats()['peak_bytes_in_use']}")
        sc.stop()
    finally:
        shutil.rmtree(root, ignore_errors=True)

    setup_s = sum(s for _n, s, is_setup in PHASES if is_setup)
    say(f"set-up total {setup_s:.1f} s; all phases "
        f"{sum(s for _n, s, _ in PHASES):.1f} s")


if __name__ == "__main__":
    main()
