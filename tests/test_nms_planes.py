"""The detectors' greedy NMS (models/detection.py): the boxes go through
the loop as four lane-dense (B, N) coordinate planes with their areas
computed once, and what it keeps is bit for bit what the former
formulation kept (a per-image scan over a (N, 4) box array under `vmap`,
kept here verbatim) and what a plain numpy greedy NMS keeps; lowered at
the 1080p frame's 73,440 anchors, no (B, N, 4) array enters the loop,
which stays under the `nms` scope."""

import functools
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from scanner_tpu.models.detection import (batched_nms, decode_boxes,
                                          make_anchors, pack_detections)

TOP_K = 32
FULL_N = 68 * 120 * 9  # the stride-16 grid of a 1080p frame, 9 anchors


# -- the former formulation, verbatim -------------------------------------

def old_decode_boxes(anchors, deltas):
    cy = anchors[:, 0] + deltas[..., 0] * anchors[:, 2]
    cx = anchors[:, 1] + deltas[..., 1] * anchors[:, 3]
    h = anchors[:, 2] * jnp.exp(jnp.clip(deltas[..., 2], -4, 4))
    w = anchors[:, 3] * jnp.exp(jnp.clip(deltas[..., 3], -4, 4))
    boxes = jnp.stack([cy - h / 2, cx - w / 2, cy + h / 2, cx + w / 2],
                      axis=-1)
    return jnp.clip(boxes, 0.0, 1.0)


@functools.partial(jax.jit, static_argnames=("top_k",))
def old_batched_nms(boxes, scores, top_k: int = 32, iou_thresh: float = 0.5):
    def one_image(b, s):
        def area(bb):
            return jnp.maximum(bb[..., 2] - bb[..., 0], 0) * \
                jnp.maximum(bb[..., 3] - bb[..., 1], 0)

        def iou(b1, b2):
            y1 = jnp.maximum(b1[0], b2[..., 0])
            x1 = jnp.maximum(b1[1], b2[..., 1])
            y2 = jnp.minimum(b1[2], b2[..., 2])
            x2 = jnp.minimum(b1[3], b2[..., 3])
            inter = jnp.maximum(y2 - y1, 0) * jnp.maximum(x2 - x1, 0)
            return inter / jnp.maximum(area(b1) + area(b2) - inter, 1e-9)

        def step(carry, _):
            sc, sel_idx, sel_sc, i = carry
            j = jnp.argmax(sc)
            best = sc[j]
            sel_idx = sel_idx.at[i].set(jnp.where(best > 0, j, -1))
            sel_sc = sel_sc.at[i].set(jnp.maximum(best, 0))
            overl = iou(b[j], b)
            sc = jnp.where(overl > iou_thresh, -1.0, sc)
            sc = sc.at[j].set(-1.0)
            return (sc, sel_idx, sel_sc, i + 1), None

        init = (s, jnp.full((top_k,), -1, jnp.int32),
                jnp.zeros((top_k,), jnp.float32), 0)
        final, _ = jax.lax.scan(step, init, None, length=top_k)
        _sc, idx, ssc, _i = final
        return idx, ssc

    return jax.vmap(one_image)(boxes, scores)


def old_pack_detections(cls, deltas, anchors, score_thresh, top_k=32):
    with jax.named_scope("nms"):
        probs = jax.nn.softmax(cls, axis=-1)[..., 1:]
        scores = probs.max(axis=-1)
        boxes = old_decode_boxes(anchors, deltas)
        idx, ssc = old_batched_nms(boxes, scores, top_k=top_k)
        sel = jnp.take_along_axis(boxes, jnp.maximum(idx, 0)[..., None],
                                  axis=1)
        valid = ((idx >= 0) & (ssc > score_thresh)).astype(jnp.float32)
        packed = jnp.concatenate([sel, ssc[..., None], valid[..., None]],
                                 axis=-1)
    return packed, sel


# -- a plain numpy greedy NMS ----------------------------------------------

def numpy_nms(boxes, scores, top_k, iou_thresh=0.5):
    """Indices kept, in the order kept, for one image ((N, 4), (N,))."""
    s = np.array(scores, np.float32)
    area = np.maximum(boxes[:, 2] - boxes[:, 0], 0) \
        * np.maximum(boxes[:, 3] - boxes[:, 1], 0)
    kept = []
    for _ in range(top_k):
        j = int(np.argmax(s))
        if s[j] <= 0:
            break
        kept.append(j)
        b = boxes[j]
        inter = np.maximum(np.minimum(b[2], boxes[:, 2])
                           - np.maximum(b[0], boxes[:, 0]), 0) \
            * np.maximum(np.minimum(b[3], boxes[:, 3])
                         - np.maximum(b[1], boxes[:, 1]), 0)
        iou = inter / np.maximum(area[j] + area - inter,
                                 np.float32(1e-9))
        s[iou > iou_thresh] = -1.0
        s[j] = -1.0
    return kept


# -- the cases ---------------------------------------------------------------

def _random_boxes(rng, b, n, size=0.3):
    c = rng.random((b, n, 2), np.float32)
    hw = rng.random((b, n, 2), np.float32) * size
    return np.clip(np.concatenate([c - hw / 2, c + hw / 2], -1),
                   0, 1).astype(np.float32)


def case_random(rng, b, n):
    return _random_boxes(rng, b, n), rng.random((b, n), np.float32)


def case_ties(rng, b, n):
    # few distinct scores and boxes repeated: exact ties everywhere, where
    # the first index has to win
    boxes = _random_boxes(rng, b, n // 4 + 1)
    boxes = np.repeat(boxes, 4, axis=1)[:, :n]
    scores = rng.integers(1, 4, (b, n)).astype(np.float32) / 4
    return boxes, scores


def case_slivers(rng, b, n):
    # zero-area boxes, and slivers clipped at the frame's edges
    boxes = _random_boxes(rng, b, n, size=0.6)
    boxes[:, 0::4, 2] = boxes[:, 0::4, 0]          # zero height
    boxes[:, 1::4, 3] = boxes[:, 1::4, 1]          # zero width
    boxes[:, 2::4, 0] = 0.0                        # clipped at the top
    boxes[:, 2::4, 2] = rng.random((b, len(range(2, n, 4))),
                                   np.float32) * 1e-3
    boxes[:, 3::4, 3] = 1.0                        # clipped at the right
    boxes[:, 3::4, 1] = 1.0 - rng.random((b, len(range(3, n, 4))),
                                         np.float32) * 1e-3
    return boxes, rng.random((b, n), np.float32)


def case_nonpositive(rng, b, n):
    scores = -rng.random((b, n), np.float32)
    scores[:, ::3] = 0.0
    return _random_boxes(rng, b, n), scores


def case_few(rng, b, n):
    # fewer than top_k positive scores a row
    scores = -rng.random((b, n), np.float32)
    for r in range(b):
        pos = rng.choice(n, size=min(n, 5 + r), replace=False)
        scores[r, pos] = rng.random(len(pos), np.float32) + 0.01
    return _random_boxes(rng, b, n), scores


CASES = {"random": case_random, "ties": case_ties, "slivers": case_slivers,
         "nonpositive": case_nonpositive, "few": case_few}


def _planes(boxes):
    return tuple(jnp.asarray(boxes[..., k]) for k in range(4))


def _check_against_numpy(boxes, scores, idx, ssc):
    idx, ssc = np.asarray(idx), np.asarray(ssc)
    for r in range(boxes.shape[0]):
        kept = numpy_nms(boxes[r], scores[r], TOP_K)
        assert idx[r, :len(kept)].tolist() == kept
        assert (idx[r, len(kept):] == -1).all()
        assert np.array_equal(ssc[r, :len(kept)], scores[r, kept])
        assert (ssc[r, len(kept):] == 0).all()


@pytest.mark.parametrize("b,n", [(1, 300), (8, 300), (8, FULL_N)],
                         ids=["b1", "b8", "b8_1080p"])
@pytest.mark.parametrize("case", sorted(CASES))
def test_nms_on_planes_keeps_bit_for_bit_what_the_box_array_scan_kept(
        case, b, n):
    rng = np.random.default_rng([sorted(CASES).index(case), b, n])
    boxes, scores = CASES[case](rng, b, n)
    idx, ssc = batched_nms(_planes(boxes), jnp.asarray(scores), top_k=TOP_K)
    old_idx, old_ssc = old_batched_nms(jnp.asarray(boxes),
                                       jnp.asarray(scores), top_k=TOP_K)
    assert idx.shape == (b, TOP_K) and idx.dtype == old_idx.dtype
    assert np.array_equal(np.asarray(idx), np.asarray(old_idx))
    assert np.array_equal(np.asarray(ssc), np.asarray(old_ssc))
    _check_against_numpy(boxes, scores, idx, ssc)


def _head(seed, b, fh, fw, spread=1.0):
    rng = np.random.default_rng(seed)
    anchors = make_anchors(fh, fw)
    n = anchors.shape[0]
    cls = rng.normal(0, 2, (b, n, 2)).astype(np.float32)
    # deltas wide enough that many boxes are clipped at the frame's edge
    deltas = rng.normal(0, spread, (b, n, 4)).astype(np.float32)
    return jnp.asarray(cls), jnp.asarray(deltas), jnp.asarray(anchors)


@pytest.mark.parametrize("b,fh,fw,spread",
                         [(1, 6, 8, 1.0), (8, 6, 8, 3.0), (8, 68, 120, 1.0)],
                         ids=["b1", "b8_edges", "b8_1080p"])
def test_packed_detections_are_bit_for_bit_the_former_ones(b, fh, fw,
                                                           spread):
    cls, deltas, anchors = _head(fh * fw + b, b, fh, fw, spread)
    packed, sel = jax.jit(functools.partial(
        pack_detections, score_thresh=0.05))(cls, deltas, anchors)
    old_packed, old_sel = jax.jit(functools.partial(
        old_pack_detections, score_thresh=0.05))(cls, deltas, anchors)
    assert packed.shape == (b, TOP_K, 6) and sel.shape == (b, TOP_K, 4)
    assert np.array_equal(np.asarray(sel), np.asarray(old_sel))
    assert np.array_equal(np.asarray(packed), np.asarray(old_packed))
    planes = decode_boxes(anchors, deltas)
    assert np.array_equal(np.stack([np.asarray(c) for c in planes], -1),
                          np.asarray(old_decode_boxes(anchors, deltas)))


# -- the layout guard ----------------------------------------------------------

def _lowered_at_1080p(pack):
    f = jax.jit(functools.partial(pack, score_thresh=0.05))
    n = FULL_N
    return f.lower(jax.ShapeDtypeStruct((8, n, 2), jnp.float32),
                   jax.ShapeDtypeStruct((8, n, 4), jnp.float32),
                   jax.ShapeDtypeStruct((n, 4), jnp.float32)
                   ).as_text(debug_info=True)


def _loops(text):
    """For every `while` of the lowering: its operand types, the header
    of the function it lies in, and that function's name."""
    out = []
    for m in re.finditer(r"stablehlo\.while\(.*\) : (.*)$", text, re.M):
        fn = text.rfind("func.func", 0, m.start())
        header = text[fn:text.index("\n", fn)]
        out.append((m.group(1), header,
                    re.search(r"@([\w.]+)\(", header).group(1)))
    return out


def _call_scopes(text, fn):
    """The expanded locations of every call to the function `fn`."""
    locs = dict(re.findall(r"^(#loc\d*) = (.*)$", text, re.M))

    def expand(ref, depth=0):
        return re.sub(r"#loc\d*", lambda m: expand(m.group(0), depth + 1)
                      if depth < 64 else m.group(0), locs[ref])
    return [expand(m.group(1)) for m in re.finditer(
        rf"call @{re.escape(fn)}\(.*loc\((#loc\d+)\)$", text, re.M)]


@pytest.mark.parametrize("pack,boxes_in_loop",
                         [(pack_detections, False),
                          (old_pack_detections, True)],
                         ids=["planes", "former_box_array"])
def test_no_box_array_enters_the_nms_loop_at_1080p(pack, boxes_in_loop):
    text = _lowered_at_1080p(pack)
    loops = _loops(text)
    assert loops, "no while loop in the lowering"
    box_array = f"tensor<8x{FULL_N}x4xf32>"
    assert any(box_array in types or box_array in header
               for types, header, _fn in loops) == boxes_in_loop
    for types, header, fn in loops:
        if not boxes_in_loop:
            assert f"tensor<8x{FULL_N}xf32>" in types
        # the loop lies in the NMS function, called under the `nms` scope
        assert fn.endswith("batched_nms")
        scopes = _call_scopes(text, fn)
        assert scopes and all("/nms/" in s for s in scopes)
