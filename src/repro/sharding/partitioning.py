"""Logical-axis partitioning rules (MaxText-style) for the production meshes.

Mesh axes: ("data", "model") single-pod, ("pod", "data", "model") multi-pod.

Parameter logical axes:
  embed   -> "data"      FSDP / ZeRO-3: gathered per layer during compute
  ff      -> "model"     tensor parallel (Megatron MLP split)
  heads   -> "model"     TP over attention heads (only when divisible)
  q_heads -> "model"|None  arch-dependent (falls back to q-sequence TP)
  vocab   -> "model"     sharded embedding / LM head
  experts -> None        expert weights: TP inside each expert (ff -> model)
  layers / state / window / conv / head_dim -> replicated

Activation logical axes:
  batch   -> ("pod", "data")
  seq     -> None  (or "model" in q-seq/context-parallel attention)
  kv_seq  -> "model" for the distributed decode cache
"""
from __future__ import annotations

import dataclasses
from typing import Any

import jax
from jax.sharding import Mesh, PartitionSpec as P

_MESH: Mesh | None = None


def set_mesh(mesh: Mesh | None) -> None:
    global _MESH
    _MESH = mesh


def get_mesh() -> Mesh | None:
    return _MESH


@dataclasses.dataclass(frozen=True)
class ShardingRules:
    params: dict[str, Any]
    acts: dict[str, Any]


def make_rules(
    *,
    multi_pod: bool = False,
    shard_heads: bool = True,
    qseq_tp: bool = False,
    fsdp: bool = True,
    batch_axes: tuple[str, ...] | None = None,
    moe_ep: bool = False,
    carry_seq_tp: bool = False,
) -> ShardingRules:
    """``batch_axes`` overrides the data-parallel axes (e.g. () for batch=1
    long-context cells where the batch cannot be sharded).  ``moe_ep`` moves
    the model axis from the expert-FFN hidden dim onto the expert dim
    (expert parallelism — requires n_experts % model_size == 0)."""
    if batch_axes is None:
        batch_axes = ("pod", "data") if multi_pod else ("data",)
    batch = (batch_axes if len(batch_axes) > 1 else
             (batch_axes[0] if batch_axes else None))
    params = {
        "embed": "data" if fsdp else None,
        "ff": None if moe_ep else "model",
        "heads": "model" if shard_heads else None,
        "kv_heads": None,       # GQA kv counts rarely divide the model axis
        "vocab": "model",
        "experts": "model" if moe_ep else None,
        "lru": "model",
        "lru_in": "data" if fsdp else None,
        "ssm_inner": "model",
        "state": None,
        "layers": None,
        "head_dim": None,
        "conv": None,
        "frames": None,
    }
    acts = {
        "batch": batch,
        "seq": "model" if qseq_tp else None,
        "kv_seq": "model",
        "embed": None,
        "heads": "model" if shard_heads else None,
        "kv_heads": None,
        # q-seq (context-parallel) mode: the seq dim owns the model axis, so
        # feature dims must stay unsharded in activation constraints
        # (PartitionSpec forbids one mesh axis on two dims)
        "ff": None if (qseq_tp or moe_ep) else "model",
        "vocab": None if qseq_tp else "model",
        "experts": "model" if moe_ep else None,
        "lru": None if qseq_tp else "model",
        "ssm_inner": None if qseq_tp else "model",
        "state": None,
        "head_dim": None,
        "layers": None,
        # saved scan-group carries: optionally seq-sharded over `model`
        # (Megatron-SP-style) to shrink remat-saved residual memory
        "seq_carry": "model" if carry_seq_tp else None,
    }
    return ShardingRules(params=params, acts=acts)


def shard(x: jax.Array, axes: tuple[str | None, ...], rules: ShardingRules | None):
    """with_sharding_constraint by logical activation axes (no-op w/o mesh)."""
    if rules is None or _MESH is None:
        return x
    spec = P(*(rules.acts.get(a) if a is not None else None for a in axes))
    return jax.lax.with_sharding_constraint(x, jax.sharding.NamedSharding(_MESH, spec))


def param_rules(rules: ShardingRules) -> dict[str, Any]:
    return rules.params


def activation_rules(rules: ShardingRules) -> dict[str, Any]:
    return rules.acts
