"""Multi-chip data-parallel inference for engine kernels.

A TPU host has several chips; an engine worker's model kernels should use
all of them.  The engine hands each kernel its visible device list
(KernelConfig.devices); `DataParallelApply` replicates the params across
those chips ONCE and dp-shards each batch's leading axis, letting GSPMD
run the jitted apply across chips with no code changes in the model
(reference kernels instead pinned one GPU per kernel instance via
KernelConfig.devices, kernel.h — on TPU one instance drives the whole
host's chips).

Uneven batches (a task's trailing partial work packet) are zero-padded to
a multiple of the device count so the sharded path — and its compiled
program — is reused, then the padding rows are sliced off the result.
"""

from __future__ import annotations

from typing import Any, List, Optional, Sequence

import numpy as np


def lowered_flops(jitfn, *args):
    """XLA cost-analysis FLOPs of one call of a jitted function (None if
    the backend/compiler does not report them).  Drives the bench's MFU
    column: achieved FLOP/s vs the chip's peak."""
    try:
        ca = jitfn.lower(*args).compile().cost_analysis()
        if isinstance(ca, (list, tuple)):
            ca = ca[0] if ca else {}
        flops = float(ca.get("flops", 0.0))
        return flops if flops > 0 else None
    except Exception:  # noqa: BLE001
        return None


class DataParallelApply:
    """Wraps a jitted `apply(params, batch)` with per-host dp sharding."""

    def __init__(self, apply_fn, params, devices: Optional[Sequence] = None):
        self._apply = apply_fn
        self.devices = list(devices or [])
        if len(self.devices) > 1:
            import jax
            from jax.sharding import (Mesh, NamedSharding,
                                      PartitionSpec as P)
            self._mesh = Mesh(np.array(self.devices), ("dp",))
            self._data_sharding = NamedSharding(self._mesh, P("dp"))
            # params live replicated on every chip from construction on;
            # execute() never re-uploads them
            self.params = jax.device_put(
                params, NamedSharding(self._mesh, P()))
        else:
            self._mesh = None
            # an instance that owns ONE chip commits its weights there:
            # init_or_restore leaves them uncommitted on the default
            # device (chip 0), so instances 1..n-1 would otherwise
            # re-ship them to their own chip on every call
            if self.devices:
                import jax
                params = jax.device_put(params, self.devices[0])
            self.params = params

    def cost_flops(self, *args):
        """XLA cost-analysis FLOPs of one apply() call on `args`."""
        return lowered_flops(self._apply, self.params, *args)

    def __call__(self, batch):
        if self._mesh is None or len(batch) == 0:
            return self._apply(self.params, batch)
        import jax
        import jax.numpy as jnp

        n = len(self.devices)
        rows = len(batch)
        pad = (-rows) % n
        if pad:
            batch = jnp.concatenate(
                [jnp.asarray(batch),
                 jnp.zeros((pad,) + tuple(batch.shape[1:]),
                           batch.dtype)])
        batch = jax.device_put(batch, self._data_sharding)
        out = self._apply(self.params, batch)
        if pad:
            out = jax.tree_util.tree_map(lambda x: x[:rows], out)
        return out
