"""paddle_tpu.serving.shardgroup — tensor-parallel replica groups.

The unit of serving dispatch becomes a **replica group**: an ordered tuple
of devices forming a single-axis ``tp`` submesh that runs ONE pjit'd decode
program spanning ICI collectives, instead of one whole-model replica per
device. The reference stack's analogue was ParallelExecutor's per-GPU SSA
graph + NCCL allreduce rings (``multi_devices_graph_pass.cc:286``); here the
group's layout is declarative — a :class:`GroupLayout` rule table maps every
``transformer_lm`` param name to a ``PartitionSpec`` over the group mesh and
XLA/GSPMD materializes the matching collectives inside the jitted step.

Layout (Megatron-style, heads over ``tp``):

- q/k/v projections column-parallel ``P(None, "tp")`` (their biases
  ``P("tp")``), attention out row-parallel ``P("tp", None)``;
- ffn fc1/gate column-parallel, fc2 row-parallel;
- embeddings, logits projection and layernorms replicated (tiny, and the
  test vocab is deliberately not divisible by tp);
- paged KV arrays ``[L, num_pages, page_size, H_kv * dh]`` sharded on the
  last dim, whose major part is the heads: ``P(None, None, None, "tp")``
  when ``H_kv`` divides by tp, so a shard holds whole heads.

Every per-shard ``PageAllocator`` geometry is identical — page ids are
global and only heads are split — so refcounts, the radix prefix cache,
CoW and trim are unchanged per shard. Any dim whose size doesn't divide
the tp degree degrades to replicated (same contract as
``parallel.sharding.param_shardings``), so one model definition runs at
any tp that divides its head counts and falls back gracefully otherwise.
"""

from __future__ import annotations

import dataclasses
import statistics
import time
from collections import deque
from typing import Dict, List, Optional, Sequence, Tuple

import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from paddle_tpu.core.enforce import enforce
from paddle_tpu.parallel.mesh import TP_AXIS, partition_devices, tp_submesh
from paddle_tpu.parallel.sharding import ShardingRules, degrade_spec, spec_for
from paddle_tpu.resilience import faults

__all__ = [
    "GroupLayout",
    "GroupStragglerWatch",
    "ReplicaGroup",
    "default_layout",
    "make_groups",
    "probe_members",
]

# The dim of the paged KV arrays [L, num_pages, page_size, H_kv * dh] that
# holds the heads (its major part; dh is the minor)
KV_HEAD_DIM = 3


@dataclasses.dataclass(frozen=True)
class ReplicaGroup:
    """An ordered device tuple + its ``tp`` submesh: the unit of dispatch.

    Device order is part of the identity — shard i of every param and KV
    page lives on ``devices[i]``, and the straggler watch reports skew by
    that index."""

    devices: Tuple[jax.Device, ...]
    name: str = ""

    def __post_init__(self):
        enforce(len(self.devices) >= 1, "ReplicaGroup needs >= 1 device")
        object.__setattr__(self, "devices", tuple(self.devices))
        if not self.name:
            object.__setattr__(
                self, "name", "group[" + ",".join(str(d.id) for d in self.devices) + "]"
            )
        object.__setattr__(self, "_mesh", tp_submesh(self.devices))

    @property
    def mesh(self) -> Mesh:
        return self._mesh

    @property
    def tp(self) -> int:
        return len(self.devices)

    def __len__(self) -> int:
        return len(self.devices)


def make_groups(tp: int, devices: Optional[Sequence] = None) -> List[ReplicaGroup]:
    """Slice the device list into ICI-contiguous replica groups of ``tp``."""
    return [
        ReplicaGroup(devs, name=f"group{i}")
        for i, devs in enumerate(partition_devices(tp, devices))
    ]


# Megatron-style rule table for transformer_lm param names. First match
# wins; anything unmatched is replicated (embeddings, logits, layernorms,
# out/fc2 biases — the row-parallel outputs are full-size after the psum).
_TRANSFORMER_LM_RULES: ShardingRules = (
    ("*/self_attn/q/w", P(None, TP_AXIS)),
    ("*/self_attn/k/w", P(None, TP_AXIS)),
    ("*/self_attn/v/w", P(None, TP_AXIS)),
    ("*/self_attn/q/b", P(TP_AXIS)),
    ("*/self_attn/k/b", P(TP_AXIS)),
    ("*/self_attn/v/b", P(TP_AXIS)),
    ("*/self_attn/out/w", P(TP_AXIS, None)),
    ("*/ffn/fc1/w", P(None, TP_AXIS)),
    ("*/ffn/gate/w", P(None, TP_AXIS)),
    ("*/ffn/fc1/b", P(TP_AXIS)),
    ("*/ffn/gate/b", P(TP_AXIS)),
    ("*/ffn/fc2/w", P(TP_AXIS, None)),
)


@dataclasses.dataclass(frozen=True)
class GroupLayout:
    """PartitionSpecs per param class over a replica group's mesh (the
    SpecLayout pattern: named axes + a spec per parameter family, except
    driven by a first-match rule table over param NAMES so the serving
    path needs no model-code cooperation).

    ``optional`` lists rule patterns allowed to match no parameter — the
    swiglu gate projections exist only in that FFN variant, so their
    rules are not dead on a relu model. Any other zero-match rule is a
    ``shard-dead-rule`` finding in ``analysis.shard_analysis`` (stale
    after a param rename, or a layout for the wrong model family).
    ``kv_rule`` overrides the default heads KV-page spec; the static
    analyzer checks it against ``PagedKVCache.geometry()`` — page-id and
    page-offset dims must stay global across the group."""

    tp_axis: str = TP_AXIS
    rules: ShardingRules = _TRANSFORMER_LM_RULES
    optional: Tuple[str, ...] = ("*/ffn/gate/w", "*/ffn/gate/b")
    kv_rule: Optional[P] = None

    def param_spec(self, name: str, shape: Tuple[int, ...], mesh: Mesh) -> P:
        spec = spec_for(name, self.rules, ndim=len(shape))
        return degrade_spec(mesh, spec, shape, name=name)

    def param_sharding(
        self, group: ReplicaGroup, name: str, shape: Tuple[int, ...]
    ) -> NamedSharding:
        return NamedSharding(group.mesh, self.param_spec(name, shape, group.mesh))

    def kv_page_spec(self, shape: Tuple[int, ...], mesh: Mesh, kv_heads: int) -> P:
        """KV pages sharded along heads, the major part of their last dim;
        degrades to replicated when ``kv_heads`` doesn't divide tp (the same
        model still serves, just without the memory win). The count decides,
        not the width: ``H_kv * dh`` may divide where ``H_kv`` does not, and
        a shard must hold whole heads."""
        if self.kv_rule is not None:
            return degrade_spec(mesh, self.kv_rule, shape, name="kv_pages")
        dims = [None] * len(shape)
        if len(shape) > KV_HEAD_DIM:
            dims[KV_HEAD_DIM] = self.tp_axis
            shape = shape[:KV_HEAD_DIM] + (kv_heads,) + shape[KV_HEAD_DIM + 1:]
        return degrade_spec(mesh, P(*dims), shape, name="kv_pages")

    def kv_page_sharding(
        self, group: ReplicaGroup, shape: Tuple[int, ...], kv_heads: int
    ) -> NamedSharding:
        return NamedSharding(group.mesh, self.kv_page_spec(shape, group.mesh, kv_heads))

    def replicated(self, group: ReplicaGroup) -> NamedSharding:
        return NamedSharding(group.mesh, P())

    def shard_params(
        self, group: ReplicaGroup, params: Dict[str, jax.Array]
    ) -> Dict[str, jax.Array]:
        """device_put every param onto the group mesh under its rule —
        the group-mode analogue of ``parallel.sharding.shard_variables``."""
        return {
            name: jax.device_put(
                v, self.param_sharding(group, name, np.shape(v))
            )
            for name, v in params.items()
        }


def default_layout() -> GroupLayout:
    return GroupLayout()


def probe_members(
    group: ReplicaGroup, *, engine_label: Optional[str] = None, nbytes: int = 1 << 12
) -> Dict[int, float]:
    """Per-member liveness/latency canary: time a small host→device
    transfer to EACH member individually (the jitted step is one fused
    program — it cannot attribute a fault or a stall to a single chip;
    this can). The ``GROUP_MEMBER`` fault point fires per shard so chaos
    can fail or stall exactly one member. Raises whatever the injected
    fault raises — the engine treats any member fault as fatal for the
    whole group."""
    payload = np.zeros(nbytes, np.uint8)
    times: Dict[int, float] = {}
    for i, dev in enumerate(group.devices):
        t0 = time.perf_counter()
        faults.inject(
            faults.GROUP_MEMBER, engine=engine_label, shard=i, device=str(dev)
        )
        jax.device_put(payload, dev).block_until_ready()
        times[i] = time.perf_counter() - t0
    return times


class GroupStragglerWatch:
    """Localize the slow chip INSIDE a group from per-shard probe timings.

    Same windowed spatial-median core as
    :class:`~paddle_tpu.watch.detectors.SkewDetector`, with one change a
    tiny group forces: the baseline for shard i is the median of the
    OTHER shards' recent means (leave-one-out). SkewDetector's spatial
    mode medians over ALL keys, which is right for a fleet of replicas
    but breaks at tp=2 — the 2-element median averages the straggler in,
    bounding the ratio below 2.0 so no sane threshold can ever fire.
    ``observe`` returns ``(worst_skew, flagged_shard)``; skew 1.0 means
    perfectly balanced."""

    def __init__(self, group: ReplicaGroup, *, ratio: float = 4.0,
                 window: int = 32, min_samples: int = 5):
        enforce(ratio > 1.0, f"skew ratio must be > 1.0, got {ratio}")
        enforce(min_samples >= 2,
                f"min_samples must be >= 2, got {min_samples}")
        self._group = group
        self.ratio = float(ratio)
        self.min_samples = int(min_samples)
        self._series: Dict[int, deque] = {
            i: deque(maxlen=window) for i in range(len(group.devices))
        }

    def observe(self, shard_times: Dict[int, float]) -> Tuple[float, Optional[int]]:
        for shard, seconds in shard_times.items():
            if shard in self._series and seconds >= 0:
                self._series[shard].append(float(seconds))
        ready = {i: s for i, s in self._series.items()
                 if len(s) >= self.min_samples}
        if len(ready) < 2:
            return 1.0, None
        means = {i: sum(s) / len(s) for i, s in ready.items()}
        flagged: Optional[int] = None
        worst = 1.0
        for shard in sorted(means):
            peers = [m for i, m in means.items() if i != shard]
            baseline = statistics.median(peers)
            if baseline <= 0:
                continue
            skew = means[shard] / baseline
            if skew > worst:
                worst = skew
                if skew > self.ratio:
                    flagged = shard
        return worst, flagged
