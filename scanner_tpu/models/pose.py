"""Pose estimation: the flagship model family.

Capability parity: reference examples/apps/pose_detection (OpenPose Caffe
kernel, main.py:50-56) — rebuilt as a TPU-native video pose network:
per-frame conv backbone -> temporal attention over the clip (ring attention
when the time axis is sharded over 'sp') -> MoE mixer -> deconv heatmap
head.  The train step shards dp (batch), sp (time), tp (channels/experts)
over one jax Mesh; XLA inserts all collectives.
"""

from __future__ import annotations

from typing import Any, Dict, Optional, Sequence, Tuple

import flax.linen as nn
import jax
import jax.numpy as jnp
import numpy as np
import optax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from ..common import DeviceType, FrameType
from ..graph.ops import Kernel, register_op
from .nets import Backbone, DeconvHead, TemporalBlock

NUM_KEYPOINTS = 17


class PipelinedTemporalStack(nn.Module):
    """The temporal trunk as an in-program pipeline: one TemporalBlock's
    parameter structure repeated `num_stages` times, stacked on a leading
    axis sharded over the mesh's 'pp' ranks, executed with the GPipe
    microbatch schedule (parallel/pp.py).  Each pp rank holds exactly one
    stage's weights — the HBM-scaling path when the trunk outgrows a
    chip.  Stages are collective-free, so sp must be 1 (dp/tp compose)."""

    mesh: Any
    num_stages: int
    num_microbatches: int = 2
    dtype: Any = jnp.bfloat16
    # forwarded to every stage's TemporalBlock; must be collective-free
    # (stages run inside shard_map — a mesh-collective attention like
    # ring/ulysses cannot nest here, which is why pp requires sp == 1)
    attn_fn: Optional[Any] = None
    # jax.checkpoint each stage call (pp is the HBM-constrained case, so
    # the trunk must honor remat like the in-module stack does)
    remat: bool = False

    @nn.compact
    def __call__(self, tokens):
        from ..parallel.pp import make_pipeline, stack_stage_params

        blk = TemporalBlock(dtype=self.dtype, attn_fn=self.attn_fn)

        def init_stages(rng):
            keys = jax.random.split(rng, self.num_stages)
            return stack_stage_params(
                [blk.init(k, tokens[:1]) for k in keys])

        stacked = self.param("stages", init_stages)
        if self.is_initializing():
            # init only creates params; the schedule needs the real
            # (dp-sharded, microbatchable) batch geometry — run one stage
            # unpipelined for output shape/dtype
            return blk.apply(
                jax.tree_util.tree_map(lambda a: a[0], stacked), tokens)
        stage = lambda p, x: blk.apply(p, x)  # noqa: E731
        if self.remat:
            stage = jax.checkpoint(stage)
        pipe = make_pipeline(self.mesh, stage,
                             num_microbatches=self.num_microbatches)
        return pipe(stacked, tokens)


class VideoPoseNet(nn.Module):
    """(B, T, H, W, 3) uint8 clip -> (B, T, H/4, W/4, K) heatmaps."""

    width: int = 32
    temporal_layers: int = 2
    keypoints: int = NUM_KEYPOINTS
    dtype: Any = jnp.bfloat16
    attn_fn: Optional[Any] = None
    # a mesh with a 'pp' axis pipelines the temporal trunk over its
    # stages (PipelinedTemporalStack); None keeps the in-module stack
    pipeline_mesh: Optional[Any] = None
    pipeline_microbatches: int = 2
    # rematerialize the backbone + temporal blocks on the backward pass
    # (jax.checkpoint): activations of the deepest trunk are recomputed
    # instead of stored — the HBM/FLOPs trade for long clips at high
    # resolution.  Same math: losses/grads match the unremat'd model.
    remat: bool = False

    @nn.compact
    def __call__(self, clip):
        B, T, H, W, _ = clip.shape
        frames = clip.reshape(B * T, H, W, 3)
        # explicit names pin the param tree to the unremat'd layout, so
        # remat toggles freely over the same weights (incl. shipped .npz)
        BackboneM = nn.remat(Backbone) if self.remat else Backbone
        feat = BackboneM(width=self.width, dtype=self.dtype,
                         name="Backbone_0")(frames)
        _, fh, fw, C = feat.shape
        # clip-level context: GAP tokens mixed across time
        tokens = feat.mean(axis=(1, 2)).reshape(B, T, C)
        if self.pipeline_mesh is not None:
            tokens = PipelinedTemporalStack(
                mesh=self.pipeline_mesh,
                num_stages=self.temporal_layers,
                num_microbatches=self.pipeline_microbatches,
                dtype=self.dtype, attn_fn=self.attn_fn,
                remat=self.remat)(tokens)
        else:
            BlockM = nn.remat(TemporalBlock) if self.remat \
                else TemporalBlock
            for li in range(self.temporal_layers):
                tokens = BlockM(dtype=self.dtype, attn_fn=self.attn_fn,
                                name=f"TemporalBlock_{li}")(tokens)
        # FiLM-style broadcast of temporal context back onto spatial maps
        scale = nn.Dense(C, dtype=self.dtype, name="film")(tokens)
        feat = feat.reshape(B, T, fh, fw, C)
        feat = feat * (1.0 + scale[:, :, None, None, :])
        heat = DeconvHead(keypoints=self.keypoints,
                          dtype=self.dtype)(feat.reshape(B * T, fh, fw, C))
        return heat.reshape(B, T, heat.shape[1], heat.shape[2],
                            self.keypoints)


def init_params(rng, clip_shape=(1, 4, 128, 128, 3), **kw):
    model = VideoPoseNet(**kw)
    clip = jnp.zeros(clip_shape, jnp.uint8)
    return model, model.init(rng, clip)


def pp_params_to_plain(params):
    """Convert a pipeline-mesh VideoPoseNet param tree (stacked stages
    under PipelinedTemporalStack_0/stages) to the plain serving layout
    (TemporalBlock_i) — train with pp, serve with the engine kernels.
    The schedule is exactly the sequential composition (parallel/pp.py),
    so converted params produce identical outputs."""
    p = dict(params["params"])
    if "PipelinedTemporalStack_0" not in p:
        return params  # already plain
    stacked = p.pop("PipelinedTemporalStack_0")["stages"]["params"]
    leaves = jax.tree_util.tree_leaves(stacked)
    S = int(leaves[0].shape[0])
    for i in range(S):
        p[f"TemporalBlock_{i}"] = jax.tree_util.tree_map(
            lambda a, i=i: np.asarray(a[i]), stacked)
    return {"params": p}


def plain_params_to_pp(params):
    """Inverse of pp_params_to_plain: stack TemporalBlock_0..S-1 (count
    derived from the tree) into the pipeline layout so plain-trained (or
    shipped) weights can continue training on a pp mesh."""
    from ..parallel.pp import stack_stage_params

    p = dict(params["params"])
    if "PipelinedTemporalStack_0" in p:
        return params  # already pipelined
    blocks = []
    while f"TemporalBlock_{len(blocks)}" in p:
        blocks.append(p.pop(f"TemporalBlock_{len(blocks)}"))
    if not blocks:
        raise ValueError("no TemporalBlock_i entries to stack")
    p["PipelinedTemporalStack_0"] = {
        "stages": {"params": stack_stage_params(blocks)}}
    return {"params": p}


def param_shardings(params, mesh: Mesh):
    """tp-shard the big tensors: dense/conv kernels on their output
    channel, MoE expert tensors on the expert dim — over a dedicated
    'ep' axis when the mesh has one, else folded onto 'tp'; pipelined
    stage stacks on 'pp'; everything else replicated.  GSPMD propagates
    the rest (per-expert matmuls shard with their weights; the routed
    sum over experts becomes an all-reduce over the expert axis)."""
    has_pp = "pp" in mesh.axis_names and mesh.shape["pp"] > 1
    expert_axis = "ep" if ("ep" in mesh.axis_names
                           and mesh.shape["ep"] > 1) else "tp"

    def spec_for(path, x):
        name = "/".join(str(p.key) for p in path
                        if hasattr(p, "key"))
        if has_pp and "stages" in name:
            # pipeline stages: each pp rank holds its own stage's weights
            return NamedSharding(
                mesh, P(*(("pp",) + (None,) * (x.ndim - 1))))
        if ("w1" in name or "w2" in name) and x.ndim == 3 \
                and x.shape[0] % mesh.shape[expert_axis] == 0:
            return NamedSharding(mesh, P(expert_axis, None, None))
        if x.ndim == 2 and x.shape[1] % mesh.shape["tp"] == 0:
            return NamedSharding(mesh, P(None, "tp"))
        if x.ndim == 4 and x.shape[3] % mesh.shape["tp"] == 0:
            return NamedSharding(mesh, P(None, None, None, "tp"))
        return NamedSharding(mesh, P())

    return jax.tree_util.tree_map_with_path(spec_for, params)


def make_train_step(model: VideoPoseNet, optimizer=None):
    opt = optimizer or optax.adam(1e-3)

    def loss_fn(params, clip, target):
        heat = model.apply(params, clip)
        return jnp.mean((heat - target) ** 2)

    def train_step(params, opt_state, clip, target):
        loss, grads = jax.value_and_grad(loss_fn)(params, clip, target)
        updates, opt_state = opt.update(grads, opt_state, params)
        params = optax.apply_updates(params, updates)
        return params, opt_state, loss

    return opt, train_step


def make_sharded_train_step(mesh: Mesh, clip_shape=(8, 8, 64, 64, 3),
                            width: int = 32,
                            attn_scheme: Optional[str] = None,
                            remat: bool = False,
                            pipeline_microbatches: int = 2,
                            temporal_layers: Optional[int] = None):
    """Build the full multi-chip training step: dp-sharded batch,
    sp-sharded time (ring attention), tp-sharded params/experts.
    Returns (jitted_step, params, opt_state, example batch).

    attn_scheme selects the sequence-parallel attention: "ring"
    (default), "pallas" (ring with the fused pallas flash kernel,
    kernels/pallas_attention.py), or "ulysses" (all-to-all head
    sharding); None reads SCANNER_TPU_ATTN (same values).

    A mesh with a 'pp' axis > 1 pipelines the temporal trunk over its
    stages (PipelinedTemporalStack / parallel/pp.py).  Pipeline stages
    are collective-free, so pp requires sp == 1 (dp and tp compose).
    `pipeline_microbatches` (M) sets the schedule's bubble fraction
    (S-1)/(M+S-1); the per-dp-shard batch must divide by M.  remat=True
    wraps backbone + temporal blocks (incl. pipeline stages) in
    jax.checkpoint — recompute activations instead of storing them.

    On a pp mesh the temporal-trunk depth IS the pipeline depth: one
    temporal block per stage.  Pass `temporal_layers` to assert the
    depth you expect — a mismatch with the pp axis size raises instead
    of silently changing the architecture with the mesh."""
    import os

    attn = None
    pp = int(mesh.shape.get("pp", 1)) if "pp" in mesh.axis_names else 1
    if pp > 1 and mesh.shape["sp"] > 1:
        raise ValueError(
            "pp > 1 requires sp == 1: pipeline stages are "
            "collective-free, so sequence-parallel attention cannot run "
            "inside a stage")
    if mesh.shape["sp"] > 1:
        scheme = attn_scheme or os.environ.get("SCANNER_TPU_ATTN", "ring")
        if scheme not in ("ring", "pallas", "ulysses"):
            raise ValueError(
                f"unknown attention scheme {scheme!r}; expected "
                "'ring', 'pallas' or 'ulysses'")
        if scheme == "ulysses":
            from ..parallel.ulysses import make_ulysses_attention
            attn = make_ulysses_attention(mesh, axis="sp")
        else:
            from ..parallel.ring_attention import make_ring_attention
            attn = make_ring_attention(
                mesh, axis="sp",
                impl="pallas" if scheme == "pallas" else "xla")
    kw = {"remat": remat}
    if pp > 1:
        if temporal_layers is not None and temporal_layers != pp:
            raise ValueError(
                f"temporal_layers={temporal_layers} but the mesh's pp axis "
                f"has {pp} stages; the pipelined trunk runs exactly one "
                "temporal block per stage, so the two must be equal "
                "(resize the pp axis or drop the argument)")
        kw.update(pipeline_mesh=mesh, temporal_layers=pp,
                  pipeline_microbatches=pipeline_microbatches)
    elif temporal_layers is not None:
        kw.update(temporal_layers=temporal_layers)
    model, params = init_params(
        jax.random.PRNGKey(0),
        clip_shape=(1,) + tuple(clip_shape[1:]), width=width,
        attn_fn=attn, **kw)
    opt, step = make_train_step(model)
    p_shard = param_shardings(params, mesh)
    params = jax.device_put(params, p_shard)
    opt_state = opt.init(params)
    data_spec = NamedSharding(mesh, P("dp", "sp"))
    B, T = clip_shape[0], clip_shape[1]
    hm_h, hm_w = clip_shape[2] // 4, clip_shape[3] // 4
    # deterministic nonzero data so the step exercises real numerics
    clip = jax.device_put(
        (np.arange(np.prod(clip_shape)) % 251).astype(np.uint8)
        .reshape(clip_shape), data_spec)
    tshape = (B, T, hm_h, hm_w, NUM_KEYPOINTS)
    target = jax.device_put(
        np.sin(np.arange(np.prod(tshape))).astype(np.float32)
        .reshape(tshape), data_spec)
    jit_step = jax.jit(step, donate_argnums=(0, 1))
    return jit_step, params, opt_state, (clip, target)


# ---------------------------------------------------------------------------
# Engine op
# ---------------------------------------------------------------------------

def heatmaps_to_keypoints(heat: np.ndarray) -> np.ndarray:
    """(h, w, K) heatmaps -> (K, 3) [x, y, score] in heatmap coords."""
    h, w, K = heat.shape
    flat = heat.reshape(-1, K)
    idx = flat.argmax(axis=0)
    scores = flat[idx, np.arange(K)]
    ys, xs = np.divmod(idx, w)
    return np.stack([xs, ys, scores], axis=1).astype(np.float32)


@register_op(device=DeviceType.TPU, batch=8)
class PoseDetect(Kernel):
    """Per-frame pose keypoints (reference pose_detection app op).

    With `checkpoint_dir=` the kernel restores trained weights (the
    reference app loads external OpenPose weights, main.py:50-56; here
    the provenance is scanner_tpu.models.pose_train).  `width` must
    match the trained configuration."""

    _shipped = "pose_blobnet_w8.npz"
    _shipped_width = 8

    def __init__(self, config, width: int = 32, seed: int = 0,
                 checkpoint_dir: Optional[str] = None,
                 pretrained: bool = True):
        super().__init__(config)
        from .checkpoint import init_or_restore, shipped_weights
        from .infer import DataParallelApply
        self.model = VideoPoseNet(width=width)
        if checkpoint_dir is None and pretrained \
                and width == self._shipped_width:
            checkpoint_dir = shipped_weights(self._shipped)

        @jax.named_scope("PoseDetect")
        def apply_and_peaks(params, clip):
            """Forward + on-device argmax: ship (B,K,3) keypoints off the
            chip, not (B,h,w,K) heatmaps — heatmaps are ~MBs per batch
            and the d2h hop is latency-bound (PERF.md §5)."""
            heat = self.model.apply(params, clip)[:, 0]   # (B, h, w, K)
            B, h, w, K = heat.shape
            flat = heat.reshape(B, h * w, K)
            idx = flat.argmax(axis=1)                     # (B, K)
            scores = jnp.take_along_axis(flat, idx[:, None, :],
                                         axis=1)[:, 0, :]
            ys, xs = idx // w, idx % w
            return jnp.stack([xs.astype(jnp.float32),
                              ys.astype(jnp.float32), scores], axis=-1)

        params = init_or_restore(
            self.model, jax.random.PRNGKey(seed),
            jnp.zeros((1, 1, 128, 128, 3), jnp.uint8), checkpoint_dir)
        # dp-shard batches over every chip the engine handed this kernel
        self._dp = DataParallelApply(jax.jit(apply_and_peaks), params,
                                     config.devices)
        self.params = self._dp.params

    def infer_cost_flops(self, batch):
        """XLA-reported FLOPs for one inference call on `batch` (for
        the bench's MFU accounting); None when unavailable."""
        return self._dp.cost_flops(jnp.asarray(batch)[:, None])

    def execute(self, frame: Sequence[FrameType]) -> Sequence[Any]:
        clip = jnp.asarray(frame)[:, None]  # (B, 1, H, W, 3)
        # (B, K, 3) [x, y, score] in heatmap coords, returned WITHOUT a
        # host sync: the column store chains device arrays and the sink
        # fetches once per task
        return self._dp(clip)
