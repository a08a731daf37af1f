"""Pallas kernel correctness under the interpreter (CPU), and the shape
of the Histogram op's device program (trace only)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from scanner_tpu.kernels import pallas_ops


def _bincount_frames(frames: np.ndarray, bins: int = 16) -> np.ndarray:
    b, c = frames.shape[0], frames.shape[-1]
    vals = (frames.astype(np.int64) * bins) // 256
    return np.stack([
        np.stack([np.bincount(vals[i, ..., j].ravel(), minlength=bins)
                  for j in range(c)]) for i in range(b)])


def _check_exact(frames: np.ndarray, bins: int = 16):
    got = np.asarray(pallas_ops.histogram_frames(
        jnp.asarray(frames), bins=bins, interpret=True))
    b, h, w, c = frames.shape
    assert got.dtype == np.int32 and got.shape == (b, c, bins)
    np.testing.assert_array_equal(got, _bincount_frames(frames, bins))
    assert (got.sum(-1) == h * w).all()


# 48 x 64 is one whole block, 33 x 41 one block that is no multiple of
# the uint8 tile in either direction, 1080 x 1920 seventeen blocks of 64
# rows with the last one ragged (masked, not padded)
@pytest.mark.parametrize("channels", [1, 3])
@pytest.mark.parametrize("batch,hw", [
    (1, (48, 64)), (4, (48, 64)), (16, (48, 64)), (17, (48, 64)),
    (1, (33, 41)), (4, (33, 41)), (17, (33, 41)),
    (1, (1080, 1920)),
])
def test_histogram_frames_matches_bincount(batch, hw, channels):
    rng = np.random.default_rng(batch * 1000 + hw[0] + channels)
    _check_exact(rng.integers(0, 256, (batch, *hw, channels),
                              dtype=np.uint8))


@pytest.mark.parametrize("value", [0, 15, 16, 255])
def test_histogram_frames_bin_edges(value):
    """A frame of one level lands whole in one bin: 15 is the last
    level of bin 0, 16 the first of bin 1."""
    frames = np.full((2, 33, 41, 3), value, np.uint8)
    _check_exact(frames)
    got = np.asarray(pallas_ops.histogram_frames(jnp.asarray(frames),
                                                 interpret=True))
    assert (got[..., value >> 4] == 33 * 41).all()


def test_pallas_histogram_matches_numpy():
    rng = np.random.RandomState(0)
    _check_exact(rng.randint(0, 256, (5, 25, 40, 1)).astype(np.uint8))


def test_pallas_histogram_frames_matches_xla():
    from scanner_tpu.kernels.imgproc import _histogram_impl
    rng = np.random.RandomState(1)
    frames = jnp.asarray(rng.randint(0, 255, (3, 48, 64, 3), np.uint8))
    got = np.asarray(pallas_ops.histogram_frames(frames, interpret=True))
    expect = np.asarray(_histogram_impl(frames))
    np.testing.assert_array_equal(got, expect)


@pytest.mark.parametrize("bins", [4, 10])
def test_pallas_histogram_padding_exact(bins):
    # rows/pixels not multiples of the tile sizes, and a block count
    # that does not divide the rows; masked rows must not leak.  bins 10
    # takes the multiply-and-shift binning, 4 the shift alone.
    frames = np.full((3, 100, 7, 1), 128, np.uint8)
    _check_exact(frames, bins=bins)
    rng = np.random.default_rng(bins)
    # 4200 pixels a row: 32-row blocks, 100 rows: the last holds 4
    _check_exact(rng.integers(0, 256, (1, 100, 4200, 1), dtype=np.uint8),
                 bins=bins)


def test_histogram_cmp_matches_bincount():
    """The compare+sum lowering fused chains trace on a TPU is
    numerically identical to the bincount path."""
    from scanner_tpu.kernels.imgproc import (_histogram_cmp_impl,
                                             _histogram_impl)
    rng = np.random.default_rng(7)
    frames = rng.integers(0, 256, size=(5, 33, 41, 3), dtype=np.uint8)
    a = np.asarray(_histogram_impl(frames))
    b = np.asarray(_histogram_cmp_impl(frames))
    assert np.array_equal(a, b)
    assert b.dtype == np.int32
    assert b.sum() == 5 * 33 * 41 * 3


def _eqns_outside_kernels(jaxpr):
    """Every equation of a jaxpr and of the jaxprs its equations call,
    a pallas_call's own body left out: that runs in VMEM."""
    for eqn in jaxpr.eqns:
        yield eqn
        if eqn.primitive.name == "pallas_call":
            continue
        for sub in jax.core.jaxprs_in_params(eqn.params):
            yield from _eqns_outside_kernels(sub)


def test_histogram_device_program_holds_no_wide_copy_of_the_packet(
        monkeypatch):
    """The regression ISSUE 32 removed, caught without a chip: the
    program a TPU runs for a staged 16 x 1080p Histogram packet is ONE
    jitted call whose only packet-sized arrays are uint8; the widening
    to int32 happens inside the pallas_call, a block at a time."""
    from scanner_tpu.common import DeviceType
    from scanner_tpu.graph.ops import KernelConfig
    from scanner_tpu.kernels.imgproc import Histogram

    monkeypatch.setattr(pallas_ops, "on_tpu", lambda: True)
    kern = Histogram(KernelConfig(device=DeviceType.TPU))
    h, w = 1080, 1920
    packet = jax.ShapeDtypeStruct((16, h, w, 3), jnp.uint8)
    closed = jax.make_jaxpr(kern.execute)(packet)
    assert [str(v.aval) for v in closed.jaxpr.outvars] == ["int32[16,3,16]"]
    # one program: the op's call is a single jit, nothing eager around it
    assert [e.primitive.name for e in closed.jaxpr.eqns] == ["jit"]
    eqns = list(_eqns_outside_kernels(closed.jaxpr))
    assert sum(e.primitive.name == "pallas_call" for e in eqns) == 1
    wide = [(e.primitive.name, str(v.aval)) for e in eqns
            for v in e.outvars
            if v.aval.dtype.itemsize > 1 and v.aval.size >= h * w]
    assert not wide, wide
