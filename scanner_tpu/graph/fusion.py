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
    feeds the fuse decision and marks the execute body as
    trace-composable — see ``Kernel.execute_traced``).  Host/python
    ops, stateful kernels, and explicit ``fuse=False`` node overrides
    break chains.  A chain extends only while its tail has exactly ONE
    consumer (an intermediate read by anything else must materialize,
    so it becomes the chain's tail instead).
  * The fuse decision is cost-driven: when the roofline ledger
    (util/coststats.py ``op_efficiency``) already classified EVERY
    member of a candidate chain as compute-bound, fusion cannot save
    HBM traffic and the chain stays staged (a fresh compile for no
    bandwidth win); any memory-bound (or not-yet-measured) member makes
    the chain worth one fused executable.
  * Stencil members fuse by composing their window math into the
    chain's input stencil: the chain's read window is the composition
    of member windows, with REPEAT_EDGE clamping applied at every
    level exactly as the staged backward dilation
    (graph/analysis.py ``derive_task_streams``) applies it.

The execution half — ``FusedKernelInstance`` composing the member
``execute_traced`` bodies into one jitted program per bucket — lives in
engine/evaluate.py.

``set_enabled(False)`` is the kill switch / A/B lever; the ``[perf]
fusion_enabled`` / ``fusion_min_chain`` config keys carry deployment
defaults (docs/guide.md).  docs/observability.md §Fusion catalogs the
series below (scanner-check SC317 pins both contracts).
"""

from __future__ import annotations

import threading
from dataclasses import dataclass
from typing import Callable, Dict, List, Optional

from ..common import DeviceType
from ..util import metrics as _mx
from ..util.log import get_logger
from . import ops as O

_log = get_logger("fusion")

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
CONFIG_KEYS = ("fusion_enabled", "fusion_min_chain")

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


# -- knobs ------------------------------------------------------------------

_ENABLED = True


def enabled() -> bool:
    return _ENABLED


def set_enabled(on: bool) -> None:
    """The kill switch ([perf] fusion_enabled config key, the tests'
    and chip_smoke.py's fused-vs-staged A/B)."""
    global _ENABLED
    _ENABLED = bool(on)


_MIN_CHAIN = 2


def fusion_min_chain() -> int:
    return _MIN_CHAIN


def set_min_chain(n: int) -> None:
    """[perf] fusion_min_chain config wiring: minimum member count for
    a chain to fuse (< 2 is meaningless — a singleton IS the staged
    path)."""
    global _MIN_CHAIN
    _MIN_CHAIN = max(2, int(n))


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
    requirement is load-bearing twice over: the planner needs the
    descriptor for the fuse decision and the chain-level roofline
    gauges, and declaring it marks the kernel's execute body as
    trace-composable (SC317 enforces the pairing with
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


def _ledger_probe(node: O.OpNode) -> Optional[str]:
    """Roofline verdict for one op from the live ledger: "compute" /
    "memory" when every measured (device, bucket) row of the op agrees
    or any row is memory-bound, None when the op was never measured."""
    try:
        from ..util import coststats as _cs
        rows = _cs.op_efficiency()
    except Exception:  # noqa: BLE001 — planning must never fail a job
        return None
    bounds = {r["bound"] for r in rows if r["op"] == node.name}
    if not bounds:
        return None
    if "memory" in bounds:
        return "memory"
    return "compute"


def plan_chains(info, min_chain: Optional[int] = None,
                probe: Optional[Callable[[O.OpNode], Optional[str]]]
                = None) -> List[FusionChain]:
    """Group maximal runs of fusable ops in `info` (a GraphInfo) into
    FusionChains.  `min_chain` defaults to the configured
    [perf] fusion_min_chain; `probe` defaults to the roofline-ledger
    verdict (tests inject their own)."""
    if min_chain is None:
        min_chain = fusion_min_chain()
    if probe is None:
        probe = _ledger_probe
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
        if len(members) < max(2, int(min_chain)):
            continue
        # cost-driven no-fuse: when the ledger already judged EVERY
        # member compute-bound, fusing saves no HBM traffic — skip the
        # fresh chain compile.  Any memory-bound or unmeasured member
        # keeps the chain.
        verdicts = [probe(m) for m in members]
        if all(v == "compute" for v in verdicts):
            _log.debug("chain %s stays staged: all members compute-bound",
                       "+".join(m.name for m in members))
            continue
        ch = FusionChain(members=members)
        chains.append(ch)
        _M_CHAINS.labels(chain=ch.chain_id).set(len(members))
    return chains


# graph key -> the chains planned for it, each the positions of its
# members in the graph's topological order; the oldest goes when there
# are _DECIDED_MAX (a key holds its ops' init args by value)
_DECIDED: Dict[tuple, List[tuple]] = {}
_DECIDED_MAX = 64
_DECIDED_LOCK = threading.Lock()


def plan_chains_once(info, key) -> List[FusionChain]:
    """`plan_chains(info)`, decided once a graph: `key` is the engine's
    `graph_key(info)` (what of a graph its evaluators are made from),
    and every later evaluator of that key plans the chains the first
    one did.  The fuse decision reads the live roofline ledger, which a
    run's own calls write: left to each evaluator, a graph could run
    fused in one run and staged in the next, two sets of programs and a
    recompile under one deployment.  None (a graph not keyable by
    value): planned each time."""
    if key is None:
        return plan_chains(info)
    key = (key, fusion_min_chain())
    with _DECIDED_LOCK:
        decided = _DECIDED.get(key)
    if decided is None:
        chains = plan_chains(info)
        at = {n.id: i for i, n in enumerate(info.ops)}
        with _DECIDED_LOCK:
            if len(_DECIDED) >= _DECIDED_MAX:
                del _DECIDED[next(iter(_DECIDED))]
            _DECIDED.setdefault(
                key, [tuple(at[m.id] for m in ch.members) for ch in chains])
        return chains
    chains = [FusionChain([info.ops[i] for i in idx]) for idx in decided]
    for ch in chains:
        _M_CHAINS.labels(chain=ch.chain_id).set(len(ch.members))
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
