"""Whole-pipeline XLA fusion planning: chains of fusable device ops.

Scanner's evaluate stage is a per-op pipeline, and the PR 9 compile
ledger + roofline gauges measure exactly what that costs: every device
op is its own jitted call, so op boundaries are dispatch/sync points and
memory-bound neighbors (Resize, Blur, HistDiff) round-trip their
intermediates through HBM when XLA could fuse them away entirely.  This
module is the planning half of ROADMAP item 3 — in the spirit of
"Automatic Full Compilation of Julia Programs and ML Models to Cloud
TPUs" (PAPERS.md), lower the whole chain to one XLA program so op
boundaries become fusion candidates:

  * ``plan_chains`` walks a ``GraphInfo`` and groups maximal runs of
    fusable device ops.  A node is fusable when it is a stateless,
    non-variadic, batched (batch > 1) single-input/single-output TPU
    kernel whose class declares a ``cost()`` descriptor (the hook both
    feeds the chain's roofline gauges and marks the execute body as
    trace-composable — see ``Kernel.execute_traced``).  Host/python
    ops, stateful kernels, and explicit ``fuse=False`` node overrides
    break chains.  A chain extends only while its tail has exactly ONE
    consumer (an intermediate read by anything else must materialize,
    so it becomes the chain's tail instead).
  * The fuse decision is the graph's alone: a run of two or more
    fusable ops is a chain, whatever a run has measured, so a graph
    plans the same chains in every evaluator of every run.
  * Stencil members fuse by composing their window math into the
    chain's input stencil: the chain's read window is the composition
    of member windows, with REPEAT_EDGE clamping applied at every
    level exactly as the staged backward dilation
    (graph/analysis.py ``derive_task_streams``) applies it.

The execution half — ``FusedKernelInstance`` composing the member
``execute_traced`` bodies into one jitted program per bucket — lives in
engine/evaluate.py.

``set_enabled(False)`` is the kill switch / A/B lever; the ``[perf]
fusion_enabled`` config key carries the deployment default
(docs/guide.md).  docs/observability.md §Fusion catalogs the
series below (scanner-check SC317 pins both contracts).
"""

from __future__ import annotations

from dataclasses import dataclass
from typing import Dict, List

from ..common import DeviceType
from ..util import metrics as _mx
from . import ops as O

# the SC317 contract: this tuple, the series registered below, and the
# marker-delimited table in docs/observability.md §Fusion may not drift
# (all pairings, both directions)
FUSION_SERIES = (
    "scanner_tpu_fusion_chains_planned",
    "scanner_tpu_fusion_chain_flops_per_s",
    "scanner_tpu_fusion_chain_bytes_per_s",
    "scanner_tpu_fusion_intermediate_bytes_saved_total",
)

# the [perf] fusion_* config keys config.default_config() must declare
# — exactly these (scanner-check SC317, both directions)
CONFIG_KEYS = ("fusion_enabled",)

_M_CHAINS = _mx.registry().gauge(
    "scanner_tpu_fusion_chains_planned",
    "Member count of each fused chain the planner formed (one labeled "
    "sample per chain id; 0 chains planned leaves the series empty).",
    labels=["chain"])
_M_CHAIN_FLOPS = _mx.registry().gauge(
    "scanner_tpu_fusion_chain_flops_per_s",
    "Achieved FLOP/s of a fused chain's measured calls (member cost() "
    "descriptors summed, joined with measured seconds), per chain id, "
    "device and bucket.",
    labels=["chain", "device", "bucket"])
_M_CHAIN_BW = _mx.registry().gauge(
    "scanner_tpu_fusion_chain_bytes_per_s",
    "Achieved HBM bandwidth of a fused chain's measured calls — the "
    "chain reads its head input and writes its tail output; "
    "intermediates never materialize — per chain id, device and "
    "bucket.",
    labels=["chain", "device", "bucket"])
_M_BYTES_SAVED = _mx.registry().counter(
    "scanner_tpu_fusion_intermediate_bytes_saved_total",
    "Intermediate HBM traffic (member output writes + next-member "
    "input reads, from the member cost() descriptors) that fused "
    "dispatch avoided materializing, per chain id and device.",
    labels=["chain", "device"])


# -- the kill switch --------------------------------------------------------

_ENABLED = True


def enabled() -> bool:
    return _ENABLED


def set_enabled(on: bool) -> None:
    """The kill switch ([perf] fusion_enabled config key, the tests'
    and chip_smoke.py's fused-vs-staged A/B)."""
    global _ENABLED
    _ENABLED = bool(on)


# a chain of one IS the staged path
_MIN_CHAIN = 2


# -- the planner ------------------------------------------------------------

@dataclass
class FusionChain:
    """One maximal run of fusable ops, head -> tail in dataflow order.
    Only the tail's output materializes; the engine composes the member
    execute bodies into one jitted program (FusedKernelInstance)."""

    members: List[O.OpNode]

    @property
    def head(self) -> O.OpNode:
        return self.members[0]

    @property
    def tail(self) -> O.OpNode:
        return self.members[-1]

    @property
    def chain_id(self) -> str:
        """The stable chain identity observability keys on: the member
        op names joined with '+' (e.g. "Resize+Blur+Histogram")."""
        return "+".join(m.name for m in self.members)

    @property
    def member_names(self) -> List[str]:
        return [m.name for m in self.members]

    def stencils(self) -> List[List[int]]:
        return [m.effective_stencil() for m in self.members]

    def windows(self) -> List[int]:
        """Per-member stencil-window length; 0 = the member takes no
        window axis (stencil [0]).  Note a 1-offset stencil like [-1]
        still carries a window axis of length 1."""
        return [len(s) if s != [0] else 0 for s in self.stencils()]

    def width(self) -> int:
        """Total read-window expansion of the composed chain stencil:
        one tail row reads `width` head-input positions."""
        w = 1
        for win in self.windows():
            w *= max(win, 1)
        return w


def fusable(node: O.OpNode) -> bool:
    """Chain eligibility for one node.  The ``cost()``-override
    requirement is load-bearing twice over: the chain-level roofline
    gauges need the descriptor, and declaring it marks the kernel's
    execute body as trace-composable (SC317 enforces the pairing with
    ``execute_traced`` overrides)."""
    if node.is_builtin or node.spec is None:
        return False
    if node.fuse is False:
        return False
    spec = node.spec
    if spec.is_stateful or spec.variadic:
        return False
    if node.warmup is not None:
        return False
    if node.effective_device() != DeviceType.TPU:
        return False
    if node.effective_batch() <= 1:
        return False
    if len(spec.input_columns) != 1 or len(spec.output_columns) != 1:
        return False
    fac = spec.kernel_factory
    if fac is None or getattr(fac, "cost", None) is O.Kernel.cost:
        return False
    return True


def plan_chains(info) -> List[FusionChain]:
    """Group maximal runs of fusable ops in `info` (a GraphInfo) into
    FusionChains."""
    chains: List[FusionChain] = []
    used: set = set()
    for n in info.ops:
        if n.id in used or not fusable(n):
            continue
        # topo order reaches the head of every maximal run first: a
        # fusable producer with this node as its single consumer would
        # already have absorbed it into `used`
        members = [n]
        used.add(n.id)
        cur = n
        while True:
            cons = info.consumers.get(cur.id, [])
            if len(cons) != 1:
                break  # externally consumed (or a sink): cur is the tail
            nxt = info.op_at(cons[0])
            if nxt.id in used or not fusable(nxt):
                break
            # a windowed op may only HEAD a chain: as the head its
            # stencil composes into the chain's input gather (same rows
            # the staged path read), but mid-chain the window would make
            # the fused program recompute every upstream member once per
            # window element — the staged stencil cache computes each
            # intermediate row exactly once, so fusing across it loses.
            sten = nxt.effective_stencil()
            if sten != [0]:
                break
            members.append(nxt)
            used.add(nxt.id)
            cur = nxt
        if len(members) < _MIN_CHAIN:
            continue
        ch = FusionChain(members=members)
        chains.append(ch)
        _M_CHAINS.labels(chain=ch.chain_id).set(len(members))
    return chains


def chain_metrics_for(chain_id: str, device: str, bucket: int,
                      cls: Dict, saved_bytes: float) -> None:
    """Refresh the chain-level roofline gauges from one measured fused
    call's cumulative classification (coststats.classify shape)."""
    b = str(int(bucket))
    _M_CHAIN_FLOPS.labels(chain=chain_id, device=device, bucket=b).set(
        cls["flops_per_s"])
    _M_CHAIN_BW.labels(chain=chain_id, device=device, bucket=b).set(
        cls["bytes_per_s"])
    if saved_bytes > 0:
        _M_BYTES_SAVED.labels(chain=chain_id, device=device).inc(
            saved_bytes)
