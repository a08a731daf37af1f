"""The backbone's 7x7 stride-4 stem (models/nets.py `Stem`): over column
blocks where H and W are multiples of 4, over the frame otherwise; either
way the convolution `nn.Conv((7, 7), strides=(4, 4), padding="SAME")`
computes, from the same parameters."""

import flax.linen as nn
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from scanner_tpu.models.nets import Backbone, ResBlock, Stem, _M_STEM


class _ConvBackbone(nn.Module):
    """`Backbone` with its stem as the `nn.Conv` it was written as."""

    width: int = 64
    depths: tuple = (2, 2, 2)
    dtype: object = jnp.bfloat16

    @nn.compact
    def __call__(self, x):
        x = x.astype(self.dtype) / 255.0
        x = nn.Conv(self.width, (7, 7), strides=(4, 4), dtype=self.dtype,
                    padding="SAME")(x)
        x = nn.relu(nn.GroupNorm(num_groups=8, dtype=self.dtype)(x))
        ch = self.width
        for stage, depth in enumerate(self.depths):
            for i in range(depth):
                stride = 2 if (i == 0 and stage > 0) else 1
                x = ResBlock(ch, stride=stride, dtype=self.dtype)(x)
            ch *= 2
        return x


def _counts():
    return {p: _M_STEM.labels(path=p).value() for p in ("s2d", "plain")}


def _perturbed(params, key):
    """The init's zero bias and unit-scale kernel made generic."""
    leaves, tree = jax.tree_util.tree_flatten(params)
    keys = jax.random.split(key, len(leaves))
    return jax.tree_util.tree_unflatten(tree, [
        a + 0.05 * jax.random.normal(k, a.shape, a.dtype)
        for a, k in zip(leaves, keys)])


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=["f32", "bf16"])
@pytest.mark.parametrize("hw,path", [((128, 96), "s2d"),
                                     ((256, 128), "s2d"),
                                     ((1080, 1920), "s2d"),
                                     ((130, 98), "plain")],
                         ids=["128x96", "256x128", "1080x1920", "130x98"])
def test_stem_matches_conv_bit_for_bit(dtype, hw, path):
    h, w = hw
    batch = 1 if h > 256 else 2
    x = jax.random.uniform(jax.random.PRNGKey(1), (batch, h, w, 3),
                           jnp.float32, -1.0, 1.0)
    conv = nn.Conv(32, (7, 7), strides=(4, 4), padding="SAME", dtype=dtype)
    stem = Stem(32, dtype=dtype)
    params = conv.init(jax.random.PRNGKey(0), x)
    p = _perturbed(params, jax.random.PRNGKey(2))
    want = conv.apply(p, x)
    before = _counts()
    got = stem.apply(p, x)
    after = _counts()
    assert got.dtype == want.dtype and got.shape == want.shape \
        == (batch, -(-h // 4), -(-w // 4), 32)
    np.testing.assert_array_equal(np.asarray(got, np.float32),
                                  np.asarray(want, np.float32))
    assert after[path] == before[path] + 1
    other = "plain" if path == "s2d" else "s2d"
    assert after[other] == before[other]


@pytest.mark.parametrize("width", [32, 64])
def test_backbone_params_and_outputs_unchanged(width):
    """Names, shapes and init values from one key are `nn.Conv`'s, so
    shipped weights and checkpoints load as they are; the whole backbone
    then gives the same features bit for bit."""
    x = jax.random.randint(jax.random.PRNGKey(3), (2, 128, 96, 3), 0, 256,
                           jnp.int32).astype(jnp.uint8)
    key = jax.random.PRNGKey(4)
    old = jax.jit(_ConvBackbone(width=width).init)(key, x)
    new = jax.jit(Backbone(width=width).init)(key, x)
    assert jax.tree_util.tree_structure(new) \
        == jax.tree_util.tree_structure(old)
    flat_old = dict(jax.tree_util.tree_flatten_with_path(old)[0])
    for path, a in jax.tree_util.tree_flatten_with_path(new)[0]:
        b = flat_old[path]
        assert a.shape == b.shape and a.dtype == b.dtype, path
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    stem = new["params"]["Conv_0"]
    assert stem["kernel"].shape == (7, 7, 3, width)
    assert stem["bias"].shape == (width,)
    p = _perturbed(new, jax.random.PRNGKey(5))
    np.testing.assert_array_equal(
        np.asarray(jax.jit(Backbone(width=width).apply)(p, x), np.float32),
        np.asarray(jax.jit(_ConvBackbone(width=width).apply)(p, x),
                   np.float32))


def test_stem_gradients_match_conv():
    """The zero taps' gradients are dropped with the pad: the kernel's,
    the bias's and the input's gradients are `nn.Conv`'s."""
    x = jax.random.normal(jax.random.PRNGKey(6), (2, 64, 64, 3))
    target = jax.random.normal(jax.random.PRNGKey(7), (2, 16, 16, 8))
    conv = nn.Conv(8, (7, 7), strides=(4, 4), padding="SAME",
                   dtype=jnp.float32)
    stem = Stem(8, dtype=jnp.float32)
    p = _perturbed(conv.init(jax.random.PRNGKey(8), x),
                   jax.random.PRNGKey(9))

    def loss(module):
        return lambda p, x: jnp.sum((module.apply(p, x) - target) ** 2)

    gp_want, gx_want = jax.grad(loss(conv), argnums=(0, 1))(p, x)
    gp_got, gx_got = jax.grad(loss(stem), argnums=(0, 1))(p, x)
    for name in ("kernel", "bias"):
        np.testing.assert_allclose(gp_got["params"][name],
                                   gp_want["params"][name],
                                   rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(gx_got, gx_want, rtol=1e-5, atol=1e-5)


def test_stem_counter_counts_each_traced_shape_once():
    stem = Stem(8)
    p = stem.init(jax.random.PRNGKey(0), jnp.zeros((1, 8, 8, 3)))
    fn = jax.jit(stem.apply)
    before = _counts()
    for _ in range(3):
        fn(p, jnp.zeros((2, 32, 48, 3)))
    mid = _counts()
    assert mid["s2d"] == before["s2d"] + 1
    fn(p, jnp.zeros((2, 64, 48, 3)))
    for _ in range(2):
        fn(p, jnp.zeros((2, 34, 50, 3)))
    after = _counts()
    assert after["s2d"] == before["s2d"] + 2
    assert after["plain"] == before["plain"] + 1
