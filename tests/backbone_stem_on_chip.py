"""Probe on one TPU chip: the backbone's 7x7 stride-4 stem in three forms,
each inside the whole `Backbone` (width 32, bfloat16) at 8 x 1080p:

    python3 tests/backbone_stem_on_chip.py [--calls 50] [--batch 8] [--out F]

- `plain`: the convolution over the 3-channel frame, as `nn.Conv` runs it;
- `w4`: width-wise space-to-depth, (B, H, W/4, 12) column blocks, the
  kernel zero-padded to 12 columns, strides (4, 1);
- `hw4`: the full 4x4 space-to-depth, the four row phases gathered by
  strided slices into 48 channels, the kernel zero-padded to 12 x 12,
  a 3x3 stride-1 convolution;
- `library`: `scanner_tpu.models.nets.Backbone` as it stands.

All run the same parameters on the same seeded uint8 frames. A line a
form: ms a call (median of `--calls`, a wait after each), the largest
|difference| of its output from `plain`'s, the compiled temporaries and
whether the compiled program still holds a copy of the (B, 1080, 1920, 3)
frame batch. Not a test (the name keeps pytest off it): it needs a TPU
and fails on any other backend.
"""

import argparse
import json
import os
import statistics
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import flax.linen as nn  # noqa: E402
import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
from flax.linen.dtypes import promote_dtype  # noqa: E402

from scanner_tpu.models.nets import Backbone, ResBlock  # noqa: E402

H, W, WIDTH = 1080, 1920, 32
DN = ("NHWC", "HWIO", "NHWC")


def stem_plain(x, k):
    return jax.lax.conv_general_dilated(x, k, (4, 4), "SAME",
                                        dimension_numbers=DN)


def stem_w4(x, k):
    B, h, w, c = x.shape
    x = x.reshape(B, h, w // 4, 4 * c)
    k = jnp.pad(k, ((0, 0), (3, 2), (0, 0), (0, 0)))
    k = k.reshape(7, 3, 4 * c, k.shape[-1])
    return jax.lax.conv_general_dilated(x, k, (4, 1), ((1, 2), (1, 1)),
                                        dimension_numbers=DN)


def stem_hw4(x, k):
    B, h, w, c = x.shape
    x = x.reshape(B, h, w // 4, 4 * c)
    x = jnp.concatenate([x[:, i::4] for i in range(4)], axis=-1)
    k = jnp.pad(k, ((3, 2), (3, 2), (0, 0), (0, 0)))
    k = k.reshape(3, 4, 3, 4 * c, k.shape[-1]).transpose(0, 2, 1, 3, 4)
    k = k.reshape(3, 3, 16 * c, k.shape[-1])
    return jax.lax.conv_general_dilated(x, k, (1, 1), ((1, 1), (1, 1)),
                                        dimension_numbers=DN)


FORMS = {"plain": stem_plain, "w4": stem_w4, "hw4": stem_hw4}


class _Stem(nn.Module):
    form: str
    features: int

    @nn.compact
    def __call__(self, x):
        k = self.param("kernel", nn.initializers.lecun_normal(),
                       (7, 7, x.shape[-1], self.features))
        b = self.param("bias", nn.initializers.zeros_init(), (self.features,))
        x, k, b = promote_dtype(x, k, b, dtype=jnp.bfloat16)
        return FORMS[self.form](x, k) + b


class ProbeBackbone(nn.Module):
    """`Backbone`'s body with the stem of `form`: the same parameter
    tree, so one set of parameters serves every form."""

    form: str
    width: int = WIDTH

    @nn.compact
    def __call__(self, x):
        x = x.astype(jnp.bfloat16) / 255.0
        x = _Stem(self.form, self.width, name="Conv_0")(x)
        x = nn.relu(nn.GroupNorm(num_groups=8, dtype=jnp.bfloat16)(x))
        ch = self.width
        for stage in range(3):
            for i in range(2):
                x = ResBlock(ch, stride=2 if (i == 0 and stage > 0) else 1)(x)
            ch *= 2
        return x


def _frame_copies(text, batch):
    tag = f"bf16[{batch},{H},{W},3]"
    return sum(1 for line in text.splitlines()
               if "copy(" in line and tag in line.split("=", 1)[-1][:60])


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--calls", type=int, default=50)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seed", type=int, default=2147485003)
    ap.add_argument("--out", default="")
    a = ap.parse_args()
    dev = jax.devices()[0]
    if dev.platform != "tpu":
        sys.exit(f"needs a TPU, found {dev.platform}")
    key = jax.random.PRNGKey(a.seed % (2 ** 31))
    frames = jax.random.randint(key, (a.batch, H, W, 3), 0, 256,
                                jnp.int32).astype(jnp.uint8)
    lib = Backbone(width=WIDTH)
    params = jax.jit(lib.init)(jax.random.fold_in(key, 1), frames[:1])
    nets = {f: ProbeBackbone(f) for f in FORMS}
    nets["library"] = lib
    assert jax.tree_util.tree_structure(
        jax.eval_shape(nets["hw4"].init, key, frames[:1])) \
        == jax.tree_util.tree_structure(params)
    rows, ref = [], None
    for name, net in nets.items():
        fn = jax.jit(net.apply)
        compiled = fn.lower(params, frames).compile()
        temp = compiled.memory_analysis().temp_size_in_bytes
        copies = _frame_copies(compiled.as_text(), a.batch)
        out = fn(params, frames).block_until_ready()
        times = []
        for _ in range(a.calls):
            t = time.perf_counter()
            fn(params, frames).block_until_ready()
            times.append((time.perf_counter() - t) * 1e3)
        out = out.astype(jnp.float32)
        if ref is None:
            ref = out
        row = {"form": name, "ms_per_call": statistics.median(times),
               "ms_min": min(times), "ms_max": max(times),
               "max_abs_diff": float(jnp.abs(out - ref).max()),
               "max_abs_out": float(jnp.abs(ref).max()),
               "temp_gb": temp / 1e9, "frame_copies": copies,
               "device": dev.device_kind, "batch": a.batch}
        rows.append(row)
        print(json.dumps(row), flush=True)
    if a.out:
        os.makedirs(os.path.dirname(os.path.abspath(a.out)), exist_ok=True)
        with open(a.out, "w") as f:
            json.dump(rows, f, indent=1)


if __name__ == "__main__":
    main()
