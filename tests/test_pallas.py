"""Pallas kernel correctness under the interpreter (CPU)."""

import jax.numpy as jnp
import numpy as np

from scanner_tpu.kernels import pallas_ops


def test_pallas_histogram_matches_numpy():
    rng = np.random.RandomState(0)
    vals = rng.randint(0, 16, (5, 1000)).astype(np.int32)
    got = np.asarray(pallas_ops.pallas_histogram(
        jnp.asarray(vals), bins=16, interpret=True))
    expect = np.stack([np.bincount(v, minlength=16) for v in vals])
    np.testing.assert_array_equal(got, expect)


def test_pallas_histogram_frames_matches_xla():
    from scanner_tpu.kernels.imgproc import _histogram_impl
    rng = np.random.RandomState(1)
    frames = jnp.asarray(rng.randint(0, 255, (3, 48, 64, 3), np.uint8))
    got = np.asarray(pallas_ops.histogram_frames(frames, interpret=True))
    expect = np.asarray(_histogram_impl(frames))
    np.testing.assert_array_equal(got, expect)


def test_pallas_histogram_padding_exact():
    # rows/pixels not multiples of the tile sizes; padding must not leak
    vals = jnp.asarray(np.full((3, 7), 2, np.int32))
    got = np.asarray(pallas_ops.pallas_histogram(vals, bins=4,
                                                 interpret=True))
    expect = np.zeros((3, 4), np.int32)
    expect[:, 2] = 7
    np.testing.assert_array_equal(got, expect)


def test_histogram_cmp_matches_bincount():
    """The TPU-fast compare+sum lowering is numerically identical to the
    bincount path (it is the default device path on TPU, PERF.md)."""
    import numpy as np

    from scanner_tpu.kernels.imgproc import (_histogram_cmp_impl,
                                             _histogram_impl)
    rng = np.random.default_rng(7)
    frames = rng.integers(0, 256, size=(5, 33, 41, 3), dtype=np.uint8)
    a = np.asarray(_histogram_impl(frames))
    b = np.asarray(_histogram_cmp_impl(frames))
    assert np.array_equal(a, b)
    assert b.dtype == np.int32
    assert b.sum() == 5 * 33 * 41 * 3
