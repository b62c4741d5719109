"""Tensor-parallel sharding layer for the serving engine.

One replica of the serving engine stops meaning "one chip" here: an
:class:`EngineSharding` binds a model config to a 1-D ``tensor`` mesh
(2-D ``expert`` x ``tensor`` for Mixtral) over an ICI slice, resolves
the family's regex partition rules through the strict
``match_partition_rules`` gate (a matrix nobody wrote a rule for can
never silently replicate), and places both the weights and the paged
KV pool:

- Weights follow Megatron discipline (``models/llama.py``
  ``llama_sharding_rules``): column-parallel wq/wk/wv/w1/w3,
  row-parallel wo/w2, vocab-parallel embeddings; Mixtral adds
  expert-parallel w1/w3/w2 over the ``expert`` axis with a replicated
  router (``mixtral_sharding_rules``).
- The KV pool is HEAD-sharded: the page-major layout
  ``[n_pages, page_size, n_kv_heads, head_dim]`` shards axis 2 (the
  kv heads) over ``tensor``, so every KV operation the engine performs —
  ``paged_append`` scatter, decode gather, spec-verify, prefix-cache
  page copy — indexes only the page/offset axes and stays
  device-local. No KV collectives exist; the only cross-device
  traffic is the two standard psums per layer (row-parallel wo / w2
  reductions) plus the exact vocab-parallel logit reduction.

Everything host-side is device-count-agnostic by construction: the
scheduler plans in tokens and slots (it cannot even import jax —
``serve/scheduler.py`` ALLOWED_IMPORTS), the prefix cache and block
allocator track page NUMBERS (one logical page = one shard-local tile
on every device), and the spec decoder proposes token ids. One
``StepPlan`` drives a 1-chip and an N-way engine identically, which is
what the tp=1 vs tp=4 token-parity tests enforce.

Composition with the replica pool is 2-D scale-out: shard within a
slice x replicate across slices. ``replica_device_groups`` partitions
the host's devices into per-replica groups; each pool replica builds
its own EngineSharding over its group and reports one ``load_report``
either way, so ``EnginePool`` and the autoscaler compose unchanged.
"""
from __future__ import annotations

from typing import Any, List, Optional, Sequence

import jax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from ray_tpu.mesh.device_mesh import create_mesh
from ray_tpu.mesh.sharding import (ShardingRules, match_partition_rules,
                                   infer_sharding)

# KV pool layout contract (models/kv_cache.py): the pool is page-major
# [n_pages, Pg, KH, D]; axis 2 is n_kv_heads, the ONLY sharded axis —
# pages/offsets stay whole on every device.
KV_POOL_SPEC = P(None, None, "tensor", None)
# Int8 pools carry per-(page, kv_head) fp32 scales [n_pages, KH]:
# same head axis sharded, so each device holds exactly the scales for
# its own page shards and quantize/dequantize stays device-local — no
# new collectives enter the KV path.
KV_SCALE_SPEC = P(None, "tensor")


def constrain_kv_pool(mesh: Mesh, pages):
    """Inside-jit sharding constraint pinning a KV pool pytree to
    the head-sharded layout on ``mesh``. Uses concrete
    NamedShardings, so it binds without a mesh context manager;
    applied to every jitted step's output pool it guarantees GSPMD
    can never reshard the pool (which would both break donation
    aliasing and introduce the KV collectives this layer exists to
    avoid). Rank-dispatches so int8 scale tensors (rank 2) pin to
    their own spec alongside the rank-4 pages."""
    return jax.tree_util.tree_map(
        lambda t: jax.lax.with_sharding_constraint(
            t, NamedSharding(
                mesh, KV_SCALE_SPEC if t.ndim == 2 else KV_POOL_SPEC)),
        pages)


class ShardingConfigError(ValueError):
    """Engine sharding that cannot work: a model dimension that does
    not divide over the requested mesh, a device count that does not
    cover it, or rules that leave a large tensor unmatched."""


def _declared(cfg, name: str):
    """What a family's config declares for sharding
    (models/llama.py ``serving_rules`` / ``tp_validate``); a config
    that declares none cannot be sharded, and never gets another
    family's."""
    declared = getattr(cfg, name, None)
    if declared is None:
        raise ShardingConfigError(
            f"{type(cfg).__name__} declares no {name}: no partition "
            f"rules exist for this family, so it cannot be sharded")
    return declared


def family_sharding_rules(cfg) -> ShardingRules:
    """Serving partition rules for a model config, as its family
    declares them.

    fsdp=False on purpose: a serving replica shards over ``tensor``
    (and ``expert`` for MoE) only — data parallelism is the replica
    POOL's job (one whole mesh per replica), not an in-mesh axis.
    """
    return _declared(cfg, "serving_rules")


def validate_tp(cfg, tp: int, ep: int = 1) -> None:
    """The family's own divisibility check; ShardingConfigError on
    any dimension that does not divide the mesh."""
    check = _declared(cfg, "tp_validate")
    try:
        check(tp, ep)
    except ValueError as e:
        raise ShardingConfigError(str(e)) from None


class EngineSharding:
    """A serving replica's mesh + partition rules + placement helpers.

    Built once per replica via :meth:`build`; the engine uses it to
    place weights and the KV pool at startup and to pin shardings at
    every host->device boundary. ``tp=1, ep=1`` is legal and places
    everything on one device — the degenerate mesh the parity tests
    lean on.
    """

    def __init__(self, mesh: Mesh, rules: ShardingRules, *,
                 tp: int, ep: int = 1):
        self.mesh = mesh
        self.rules = rules
        self.tp = int(tp)
        self.ep = int(ep)
        self.kv_sharding = NamedSharding(mesh, KV_POOL_SPEC)
        self.kv_scale_sharding = NamedSharding(mesh, KV_SCALE_SPEC)
        self.replicated = NamedSharding(mesh, P())

    def _kv_sharding_for(self, t):
        # rank dispatch: rank-4 page pools vs rank-2 scale tensors
        # (int8 mode) — both sharded on their kv-head axis
        return (self.kv_scale_sharding if getattr(t, "ndim", 4) == 2
                else self.kv_sharding)

    @classmethod
    def build(cls, cfg, *, tp: int = 1, ep: int = 1,
              devices: Optional[Sequence[jax.Device]] = None,
              rules: Optional[ShardingRules] = None) -> "EngineSharding":
        """Validate ``cfg`` against a ``tp`` x ``ep`` mesh and build it.

        ``devices`` defaults to the first ``tp*ep`` of
        ``jax.devices()``; passing an explicit subset is how pool
        replicas land on disjoint slices (``replica_device_groups``).
        """
        validate_tp(cfg, tp, ep)
        n_need = tp * ep
        if devices is None:
            devices = jax.devices()
        devices = list(devices)
        if len(devices) < n_need:
            raise ShardingConfigError(
                f"tp={tp} x ep={ep} needs {n_need} devices, have "
                f"{len(devices)}")
        mesh = create_mesh({"tensor": tp, "expert": ep},
                           devices=devices[:n_need])
        if rules is None:
            rules = family_sharding_rules(cfg)
        return cls(mesh, rules, tp=tp, ep=ep)

    # -- placement ---------------------------------------------------

    def shard_params(self, params):
        """Device-put the weight pytree per the rules, through the
        strict unmatched-path gate: every >=2-D tensor must be covered
        by an explicit rule or this raises (ShardingConfigError) —
        a silently replicated weight matrix costs a full copy of
        itself in every device's HBM."""
        try:
            match_partition_rules(self.rules, params,
                                  on_unmatched="raise")
        except ValueError as e:
            raise ShardingConfigError(str(e)) from None
        shardings = infer_sharding(params, self.rules, self.mesh)
        return jax.device_put(params, shardings)

    def place_kv_pool(self, pages: List[Any]):
        """Head-shard the paged KV pool: each layer's (pages_k,
        pages_v) splits axis 2 (kv heads) over ``tensor``. Page
        indices and in-page offsets are global coordinates valid on
        every device, so the host-side allocator / prefix cache /
        page tables need no changes. Int8 layers are 4-tuples (pages
        + per-page scales); rank-2 scale tensors pin to KV_SCALE_SPEC
        next to their head-sharded pages."""
        return [tuple(jax.device_put(t, self._kv_sharding_for(t))
                      for t in layer) for layer in pages]

    def replicate(self, x):
        """Commit a host value to the mesh replicated — the placement
        for page tables, positions, token chunks, and RNG keys (small
        operands every device needs whole)."""
        return jax.device_put(x, self.replicated)

    def constrain_kv(self, pages):
        return constrain_kv_pool(self.mesh, pages)

    def describe(self) -> dict:
        return {"tp": self.tp, "ep": self.ep,
                "devices": int(self.tp * self.ep)}


def replica_device_groups(n_replicas: int, devices_per_replica: int,
                          devices: Optional[Sequence[jax.Device]] = None,
                          ) -> List[List[jax.Device]]:
    """Partition the host's devices into per-replica groups for 2-D
    scale-out (replicate across slices x shard within a slice).

    Groups are disjoint while devices last. On the forced-multi-device
    CPU host the pool tests run on, further replicas wrap around
    (replica i reuses the group at ``i % n_full_groups``); on real
    chips time-sharing a device is never what was asked for, so a
    replica beyond the last full group raises ShardingConfigError.
    """
    if n_replicas <= 0 or devices_per_replica <= 0:
        raise ShardingConfigError(
            f"need n_replicas >= 1 and devices_per_replica >= 1, got "
            f"{n_replicas} x {devices_per_replica}")
    if devices is None:
        devices = jax.devices()
    devices = list(devices)
    if len(devices) < devices_per_replica:
        raise ShardingConfigError(
            f"devices_per_replica={devices_per_replica} exceeds the "
            f"{len(devices)} visible devices")
    n_full = len(devices) // devices_per_replica
    if n_replicas > n_full and devices[0].platform != "cpu":
        raise ShardingConfigError(
            f"{n_replicas} replicas x {devices_per_replica} device(s) "
            f"do not fit the {len(devices)} {devices[0].platform} "
            f"devices of this host")
    groups = []
    for i in range(n_replicas):
        j = i if i < n_full else i % n_full
        lo = j * devices_per_replica
        groups.append(devices[lo:lo + devices_per_replica])
    return groups
