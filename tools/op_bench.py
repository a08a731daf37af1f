"""Per-op device/host A/B bench (the reference DeviceTestBench analog,
py_test.py:438 — CPU-vs-GPU benches of the same op).

For every hot op (kernel stdlib + model zoo inference) this tool runs the
same computation on the host CPU backend and on the accelerator, checks
the results agree, and reports throughput for both.  Forced completion:
every timed repetition device_gets a scalar that depends on the result.

Run: python tools/op_bench.py [--reps N]
Output: one JSON line per op to stdout + OP_BENCH.json at the repo root;
on a host with no reachable accelerator the device columns are absent
(the tool still validates and times the host paths).
"""

import argparse
import json
import os
import sys
import time

import numpy as np

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)
OUT = os.path.join(REPO, "OP_BENCH.json")

BATCH, H, W = 16, 480, 640

# op name -> ABSOLUTE max_abs_diff allowed for host/device agreement.
# Histograms are integer counts (bit exact); resize/blur are uint8 with
# f32-vs-bf16 interpolation, so one rounding count of slack.  Model
# inference rows get no verdict: trained nets on random-noise frames have
# near-tied argmaxes/scores, so cross-backend diffs are expected — the
# tool records max_abs_diff as information only (engine-level model
# equivalence is pinned by the test suite on real scene fixtures).
ATOL = {
    "histogram_cmp": 0.0,
    "histogram_pallas": 0.0,
    "resize_320x240": 1.0,
    "blur": 1.0,
    # integer fixed-point conversion: bit-exact across backends
    "yuv420_to_rgb": 0.0,
}
# device-only ops validated against a host op with identical semantics
REF_OP = {"histogram_pallas": "histogram_cmp"}


def _force(x) -> float:
    """Materialize a scalar that depends on every result element."""
    import jax
    import jax.numpy as jnp
    leaves = [jnp.asarray(l) for l in jax.tree_util.tree_leaves(x)]
    return float(jax.device_get(sum(jnp.sum(l.astype(jnp.float32))
                                    for l in leaves)))


def _bench(fn, batch, reps) -> float:
    """Frames/sec over `reps` forced repetitions (first call warms jit)."""
    import jax
    import jax.numpy as jnp
    _force(fn(batch))
    t0 = time.time()
    acc = None
    for _ in range(reps):
        r = fn(batch)
        s = sum(jnp.sum(jnp.asarray(l).astype(jnp.float32))
                for l in jax.tree_util.tree_leaves(r))
        acc = s if acc is None else acc + s
    _ = float(jax.device_get(acc))
    return BATCH * reps / (time.time() - t0)


def _make_cases(dev):
    """(name, fn) pairs built for `dev` (the active default device), so
    model params live where the computation runs.  fn maps a resident
    (B, H, W, 3) uint8 batch to a pytree of arrays."""
    from scanner_tpu.common import DeviceType
    from scanner_tpu.graph.ops import KernelConfig, registry
    import scanner_tpu.models  # noqa: F401  (registers model ops)
    import scanner_tpu.kernels  # noqa: F401
    from scanner_tpu.kernels.imgproc import (_blur_impl,
                                             _gaussian_kernel1d,
                                             _histogram_cmp_impl,
                                             _resize_impl)

    cfg = KernelConfig(device=DeviceType.TPU, devices=[dev])

    def model(name, **kw):
        kern = registry.get(name).kernel_factory(cfg, **kw)
        return lambda b: kern.execute(b)

    import jax.numpy as jnp
    gk = jnp.asarray(_gaussian_kernel1d(5, 1.5))
    cases = [
        ("histogram_cmp", lambda b: _histogram_cmp_impl(b)),
        ("resize_320x240", lambda b: _resize_impl(b, 240, 320)),
        ("blur", lambda b: _blur_impl(b.astype(jnp.float32), gk, 5)),
        ("pose_infer_w8", model("PoseDetect", width=8)),
        ("objdet_infer_w8", model("ObjectDetect", width=8)),
        ("seg_infer_w8", model("InstanceSegment", width=8)),
        ("embed_infer_w8", model("FaceEmbedding", width=8)),
    ]
    if dev.platform == "tpu":
        from scanner_tpu.kernels.pallas_ops import histogram_frames
        cases.insert(1, ("histogram_pallas",
                         lambda b: histogram_frames(b)))

    # the YUV420-wire on-device conversion (kernels/color.py): input is
    # flat I420 rows rather than the shared RGB batch — built lazily on
    # the active device, same bytes both backends (agreement bit-exact)
    def yuv_case():
        import jax

        from scanner_tpu.kernels.color import yuv420_to_rgb_device
        from scanner_tpu.video.lib import yuv420_frame_bytes
        state = {}

        def fn(_b):
            if "flat" not in state:
                r = np.random.RandomState(1)
                state["flat"] = jax.device_put(r.randint(
                    0, 256, (BATCH, yuv420_frame_bytes(H, W)), np.uint8))
            return yuv420_to_rgb_device(state["flat"], H, W)

        return fn

    cases.append(("yuv420_to_rgb", yuv_case()))
    return cases


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--reps", type=int, default=10)
    args = ap.parse_args()

    import jax

    accel = next((d for d in jax.devices() if d.platform != "cpu"), None)
    try:
        cpu = jax.devices("cpu")[0]
    except Exception:
        cpu = None

    rng = np.random.RandomState(0)
    host_batch = rng.randint(0, 255, (BATCH, H, W, 3), dtype=np.uint8)
    rows = {}
    for label, dev in (("host", cpu), ("device", accel)):
        if dev is None:
            continue
        with jax.default_device(dev):
            for name, fn in _make_cases(dev):
                row = rows.setdefault(name, {"op": name})
                try:
                    batch = jax.device_put(host_batch, dev)
                    row[f"{label}_fps"] = round(
                        _bench(fn, batch, args.reps), 1)
                    row[f"_{label}_out"] = jax.device_get(fn(batch))
                except Exception as e:  # noqa: BLE001
                    row[f"{label}_error"] = \
                        f"{type(e).__name__}: {str(e)[:160]}"

    host_outs = {name: row.get("_host_out") for name, row in rows.items()}
    for name, row in rows.items():
        ref = row.pop("_host_out", None)
        if ref is None and name in REF_OP:
            # device-only lowering: validate against the host op with the
            # same output contract
            ref = host_outs.get(REF_OP[name])
            row["reference_op"] = REF_OP[name]
        got = row.pop("_device_out", None)
        if ref is not None and got is not None:
            import jax
            ref_leaves = jax.tree_util.tree_leaves(ref)
            got_leaves = jax.tree_util.tree_leaves(got)
            if len(ref_leaves) != len(got_leaves):
                # zip() would truncate and silently under-report the diff
                row["max_abs_diff"] = (
                    f"STRUCTURE MISMATCH: {len(ref_leaves)} host leaves "
                    f"vs {len(got_leaves)} device leaves")
                if name in ATOL:
                    row["agrees"] = False
            else:
                shapes = [(np.shape(a), np.shape(b))
                          for a, b in zip(ref_leaves, got_leaves)]
                bad = [s for s in shapes if s[0] != s[1]]
                if bad:
                    row["max_abs_diff"] = (
                        f"SHAPE MISMATCH: host {bad[0][0]} vs device "
                        f"{bad[0][1]}")
                    if name in ATOL:
                        row["agrees"] = False
                else:
                    diffs = [float(np.abs(np.asarray(a, np.float32) -
                                          np.asarray(b, np.float32)).max())
                             for a, b in zip(ref_leaves, got_leaves)]
                    row["max_abs_diff"] = max(diffs) if diffs else 0.0
                    if name in ATOL:
                        row["agrees"] = bool(
                            row["max_abs_diff"] <= ATOL[name])
        if "host_fps" in row and "device_fps" in row:
            row["speedup"] = round(
                row["device_fps"] / max(row["host_fps"], 1e-9), 1)
        print(json.dumps(row), flush=True)

    result = {"batch": [BATCH, H, W, 3], "reps": args.reps,
              "clock": time.strftime("%Y-%m-%dT%H:%M:%S"),
              "ops": list(rows.values())}
    with open(OUT, "w") as f:
        json.dump(result, f, indent=1)
    return 0


if __name__ == "__main__":
    sys.exit(main())
