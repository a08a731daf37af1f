"""The controls of `reid_dense` at the cell's own size, and the program's
ops beside them:

    python3 benchmark/tests/reid_controls_on_chip.py --seeds 1,2,3

For each seed: the seeded clip, ingest, the sample a run would compare
(drawn as a run draws it, from the set-up requests), its wire frames by
the host decode.  Then each of the reference's `CONTROLS` is put in the
program's place, and last `None`: the program's own ops, called here on
the sample's RGB with the seed's weights, outside the engine (what the
cell's run commits, less the engine's staging).  For `None` the record
also has what the comparison cannot see: how far the program's score
lies from the reference's at the box TopBox took, in log-odds, how far
below TopBox's own detection in the reference that box's log-odds stand
(what DET_TIE has to hold), and how far its corners lie from the
reference's.  Prints one JSON line per seed
and control.  Each control has to come out as not correct; `None` as
correct.
"""

import argparse
import json
import os
import shutil
import sys
import tempfile

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
ROOT = os.path.dirname(BENCH)
sys.path[:0] = [BENCH, ROOT]

WORKLOAD = "reid_dense"


def program_rows(cfg, rgb, op_args, block=8):
    """The (4 + dim,) rows the program's ops make of (n, H, W, 3) frames,
    and per frame (its TopBox box, that detection's score)."""
    import numpy as np

    from graphs import reid
    from scanner_tpu import DeviceType
    from scanner_tpu.graph import ops as O
    from scanner_tpu.models import unpack_detections

    def make(name):
        args = dict(next(o for o in cfg["graph"]["ops"]
                         if o["op"] == name).get("args", {}),
                    **op_args.get(name, {}))
        return O.registry.canonical_factory(O.registry.get(name))(
            O.KernelConfig(device=DeviceType.TPU, args=args), **args)

    det, crop, emb = (make(n) for n in ("ObjectDetect", "CropResize",
                                        "FaceEmbedding"))
    rows, picks = [], []
    for s in range(0, len(rgb), block):
        frames = rgb[s:s + block]
        packed = np.asarray(det.execute(frames))
        boxes = np.stack([reid.TopBox(None, p) for p in packed])
        for p, b in zip(packed, boxes):
            d = unpack_detections(p)
            hit = np.flatnonzero((d["boxes"] == b).all(1))
            picks.append((b, float(d["scores"][hit[0]]) if len(hit)
                          else None))
        feats = np.asarray(emb.execute(crop.execute(frames, boxes)))
        rows.extend(reid.IndexRow(None, b, f) for b, f in zip(boxes, feats))
    return rows, picks


def probe(cfg, rgb, picks, seed):
    """What the comparison cannot see, over the sample, at the anchor
    whose reference box lies nearest the program's: the largest
    |program - reference| log-odds of its score, the largest lead of
    TopBox's detection in the reference over it in log-odds, the largest
    corner distance."""
    import jax
    import numpy as np

    from reference import ReidFeatures as R
    det = cfg["graph"]["detector"]
    classes = next(o for o in cfg["graph"]["ops"]
                   if o["op"] == "ObjectDetect")["args"]["num_classes"]
    h, w = cfg["video"]["height"], cfg["video"]["width"]
    detector, _, _ = R._nets()
    params = R.init_params(seed, cfg)
    anc = R.anchors(cfg, h, w)
    out = {"odds_err": 0.0, "lead": 0.0, "corner": 0.0, "whole_frame": 0}
    with jax.default_matmul_precision("highest"):
        for s in range(0, len(rgb), 4):
            sc, odds, bx = (np.asarray(a) for a in detector(
                params["det"], rgb[s:s + 4], anc, classes, det["delta_clip"]))
            for b in range(len(sc)):
                box, score = picks[s + b]
                top = R.top_box(sc[b], bx[b], det)[1]
                if score is None or top is None:
                    out["whole_frame"] += 1
                    continue
                j = int(np.abs(bx[b] - box).sum(1).argmin())
                mine = np.log(score) - np.log1p(-min(score, 1 - 2 ** -24))
                out["odds_err"] = max(out["odds_err"],
                                      abs(mine - float(odds[b][j])))
                out["lead"] = max(out["lead"],
                                  float(odds[b][top] - odds[b][j]))
                out["corner"] = max(out["corner"],
                                    float(np.abs(bx[b][j] - box).max()))
    return out


def controls(manifest, seed, overrides=None, workload=WORKLOAD):
    """One seed's records, one a control and one for `None`.
    `overrides` shrinks the cell for a CPU test (harness.load_cell)."""
    import numpy as np

    import harness
    from reference import wire
    spec, cfg, traffic = harness.load_cell(manifest, workload, overrides)
    workdir = tempfile.mkdtemp(prefix="scbench_ctl_")
    try:
        cell = harness.Cell(cfg, traffic, seed, spec["chips"], workdir)
        ref = cell.reference
        covered = [{"request": r} for r in cell.plan["warm"]]
        sample = harness.check_sample(
            traffic, covered, np.random.default_rng([seed, 3]))
        wires = [f for r, j, lo, hi in sample for f in cell.wire(
            r["request"][j]["table"], r["request"][j]["rows"][lo:hi])]
        cell.sc.stop()
        h, w = cfg["video"]["height"], cfg["video"]["width"]
        rgb = np.stack([wire.to_rgb(f, h, w) for f in wires])
        out = []
        for control in ref.CONTROLS + (None,):
            more = {}
            if control is None:
                outputs, picks = program_rows(cfg, rgb, cell.op_args)
                more = probe(cfg, rgb, picks, seed)
            else:
                outputs = [None] * len(wires)
            values = ref.compare(cfg, wires, outputs, control=control,
                                 seed=seed)
            over = sorted(k for k in ref.LIMITS
                          if values[k] > ref.LIMITS[k])
            out.append({"workload": workload, "seed": seed,
                        "rows": len(wires), "control": control,
                        "values": values, "limits": ref.LIMITS,
                        "over": over, "not_correct": bool(over), **more})
        return out
    finally:
        shutil.rmtree(workdir, ignore_errors=True)


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--seeds", required=True)
    args = ap.parse_args()

    import harness
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        manifest = json.load(f)
    harness.build_native()
    for seed in map(int, args.seeds.split(",")):
        for rec in controls(manifest, seed):
            print(json.dumps(rec), flush=True)


if __name__ == "__main__":
    main()
