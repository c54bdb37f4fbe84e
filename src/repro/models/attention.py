"""Attention layers: GQA/MHA/MQA projections + RoPE + flash kernel dispatch,
sliding-window variants, KV caches, and a distributed decode path.

Decode caches are sharded along the *sequence* axis of the KV cache over the
"model" mesh axis (works for every kv-head count, unlike head sharding) and
combined with the flash LSE trick inside ``shard_map`` — each device scores
its local KV chunk, then a psum/pmax merge reconstructs exact softmax.
"""
from __future__ import annotations

from typing import Any

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from ..configs.base import ModelConfig
from ..kernels import ops
from ..sharding import get_mesh, shard
from .common import ParamDef, apply_rope, checkpoint_name

__all__ = [
    "attn_defs",
    "attention",
    "decode_attention",
    "init_kv_cache_defs",
]

_NEG = -1e30


def attn_defs(cfg: ModelConfig, *, cross: bool = False) -> dict[str, ParamDef]:
    d, h, kvh, hd = cfg.d_model, cfg.n_heads, cfg.n_kv_heads, cfg.resolved_head_dim
    defs: dict[str, ParamDef] = {
        "wq": ParamDef((d, h, hd), ("embed", "heads", "head_dim")),
        "wk": ParamDef((d, kvh, hd), ("embed", "kv_heads", "head_dim")),
        "wv": ParamDef((d, kvh, hd), ("embed", "kv_heads", "head_dim")),
        "wo": ParamDef((h, hd, d), ("heads", "head_dim", "embed")),
    }
    if cfg.qkv_bias:
        defs["bq"] = ParamDef((h, hd), ("heads", "head_dim"), init="zeros")
        defs["bk"] = ParamDef((kvh, hd), ("kv_heads", "head_dim"), init="zeros")
        defs["bv"] = ParamDef((kvh, hd), ("kv_heads", "head_dim"), init="zeros")
        defs["bo"] = ParamDef((d,), ("embed",), init="zeros")
    return defs


def _project_qkv(cfg: ModelConfig, p, x, kv_x=None):
    """x: (B, S, E) -> q (B,S,H,HD), k/v (B,Skv,KVH,HD)."""
    kv_x = x if kv_x is None else kv_x
    q = jnp.einsum("bse,ehd->bshd", x, p["wq"].astype(x.dtype))
    k = jnp.einsum("bse,ehd->bshd", kv_x, p["wk"].astype(x.dtype))
    v = jnp.einsum("bse,ehd->bshd", kv_x, p["wv"].astype(x.dtype))
    if cfg.qkv_bias:
        q = q + p["bq"].astype(x.dtype)
        k = k + p["bk"].astype(x.dtype)
        v = v + p["bv"].astype(x.dtype)
    return q, k, v


def attention(
    cfg: ModelConfig,
    p: dict[str, Any],
    x: jax.Array,                     # (B, S, E)
    *,
    positions: jax.Array,             # (S,) absolute positions
    causal: bool = True,
    window: int | None = None,
    rope: bool = True,
    kv_x: jax.Array | None = None,    # cross-attention source (B, Skv, E)
    rules=None,
    return_kv: bool = False,
):
    """Full-sequence attention (training / prefill / encoder)."""
    q, k, v = _project_qkv(cfg, p, x, kv_x)
    if rope:
        q = apply_rope(q, positions, cfg.rope_theta)
        k = apply_rope(k, positions, cfg.rope_theta)
    q = shard(q, ("batch", "seq", "heads", None), rules)
    k = shard(k, ("batch", "seq", "kv_heads", None), rules)
    v = shard(v, ("batch", "seq", "kv_heads", None), rules)
    q = checkpoint_name(q, "attn_q")
    k = checkpoint_name(k, "attn_kv")
    v = checkpoint_name(v, "attn_kv")
    out = ops.flash_attention(q, k, v, causal=causal, window=window)
    out = checkpoint_name(out, "attn_out")
    y = jnp.einsum("bshd,hde->bse", out, p["wo"].astype(x.dtype))
    if cfg.qkv_bias:
        y = y + p["bo"].astype(x.dtype)
    y = shard(y, ("batch", "seq", "embed"), rules)
    if return_kv:
        return y, (k, v)
    return y


# --------------------------------------------------------------------------- #
# decode path                                                                  #
# --------------------------------------------------------------------------- #
def init_kv_cache_defs(cfg: ModelConfig, batch: int, max_len: int) -> dict[str, ParamDef]:
    kvh, hd = cfg.n_kv_heads, cfg.resolved_head_dim
    dt = jnp.dtype(cfg.dtype)
    return {
        "k": ParamDef((batch, max_len, kvh, hd), ("batch", "kv_seq", "kv_heads", "head_dim"),
                      init="zeros", dtype=dt),
        "v": ParamDef((batch, max_len, kvh, hd), ("batch", "kv_seq", "kv_heads", "head_dim"),
                      init="zeros", dtype=dt),
    }


def _local_decode(q, k_cache, v_cache, k_new, v_new, slot, chunk_start, scale,
                  pos_abs, total_len, ring: bool):
    """Per-shard decode attention: update local cache chunk, partial softmax.

    q: (B, H, HD); caches: (B, C, KVH, HD); slot: scalar write index into the
    full cache (== pos for linear caches, pos % window for rings); pos_abs:
    absolute token position.  Returns (o_partial, m_local, s_local, k', v').
    """
    b, c, kvh, hd = k_cache.shape
    h = q.shape[1]
    g = h // kvh
    local_slot = slot - chunk_start
    idx = jnp.clip(local_slot, 0, c - 1)
    upd_k = jax.lax.dynamic_update_slice(k_cache, k_new[:, None], (0, idx, 0, 0))
    upd_v = jax.lax.dynamic_update_slice(v_cache, v_new[:, None], (0, idx, 0, 0))
    hit = (local_slot >= 0) & (local_slot < c)
    new_k = jnp.where(hit, upd_k, k_cache)
    new_v = jnp.where(hit, upd_v, v_cache)

    qg = (q * scale).astype(jnp.float32).reshape(b, kvh, g, hd)
    logits = jnp.einsum("bcgd,bkcd->bcgk", qg, new_k.astype(jnp.float32))
    k_slot = chunk_start + jnp.arange(c)
    # linear cache: slots <= write slot are live.  ring cache: additionally,
    # every slot is live once the ring has wrapped (pos_abs >= window).
    valid = k_slot <= slot
    if ring:
        valid = valid | (pos_abs >= total_len)
    logits = jnp.where(valid[None, None, None, :], logits, _NEG)
    m = jnp.max(logits, axis=-1, keepdims=True)               # (B,KVH,G,1)
    e = jnp.exp(logits - m)
    e = jnp.where(valid[None, None, None, :], e, 0.0)
    s = jnp.sum(e, axis=-1, keepdims=True)
    o = jnp.einsum("bcgk,bkcd->bcgd", e, new_v.astype(jnp.float32))
    return o, m[..., 0], s[..., 0], new_k, new_v


def decode_attention(
    cfg: ModelConfig,
    p: dict[str, Any],
    x: jax.Array,                 # (B, 1, E)
    cache: dict[str, jax.Array],  # {"k","v"}: (B, S_max, KVH, HD)
    pos: jax.Array,               # scalar int32 — current position
    *,
    rope: bool = True,
    window: int | None = None,
    rules=None,
):
    """Single-token decode with a (possibly seq-sharded) KV cache.

    With a mesh: shard_map over the "model" axis — each device holds a KV-seq
    chunk, computes a partial flash combine, then pmax/psum merge.  Without a
    mesh (smoke tests): single-shard fast path, same math.

    Sliding-window caches (window is not None) are rings of size S_max =
    window: slot = pos % window, all slots valid once written.
    """
    b, _, _ = x.shape
    q, k_new, v_new = _project_qkv(cfg, p, x)
    if rope:
        pos_b = jnp.full((1,), 0, jnp.int32) + pos
        q = apply_rope(q, pos_b, cfg.rope_theta)
        k_new = apply_rope(k_new, pos_b, cfg.rope_theta)
    q1 = q[:, 0]                   # (B, H, HD)
    kn, vn = k_new[:, 0], v_new[:, 0]
    scale = cfg.resolved_head_dim ** -0.5
    s_max = cache["k"].shape[1]
    ring = window is not None
    slot = pos % s_max if ring else pos

    mesh = get_mesh()
    if (
        mesh is not None
        and "model" in mesh.axis_names
        and mesh.shape["model"] > 1
        and s_max % mesh.shape["model"] == 0
    ):
        n_shards = mesh.shape["model"]
        chunk = s_max // n_shards
        # batch stays sharded over the data axes; the kv-seq shards live on
        # "model" and are combined with a pmax/psum flash merge.
        ba = rules.acts.get("batch") if rules is not None else None
        b_ax = ba if q1.shape[0] > 1 else None

        def shard_fn(q1_, kc_, vc_, kn_, vn_, pos_, slot_):
            sid = jax.lax.axis_index("model")
            o, m, s, new_k, new_v = _local_decode(
                q1_, kc_, vc_, kn_, vn_, slot_, sid * chunk, scale, pos_, s_max, ring
            )
            m_g = jax.lax.pmax(m, "model")
            corr = jnp.exp(m - m_g)
            o = jax.lax.psum(o * corr[..., None], "model")
            s = jax.lax.psum(s * corr, "model")
            out = o / jnp.maximum(s[..., None], 1e-30)
            return out, new_k, new_v

        in_specs = (
            P(b_ax, None, None),                      # q1: batch-sharded, model-replicated
            P(b_ax, "model", None, None),             # k cache: kv-seq sharded
            P(b_ax, "model", None, None),
            P(b_ax, None, None),
            P(b_ax, None, None),
            P(), P(),
        )
        out_specs = (P(b_ax, None, None, None), P(b_ax, "model", None, None),
                     P(b_ax, "model", None, None))
        out, new_k, new_v = jax.shard_map(
            shard_fn, mesh=mesh, in_specs=in_specs, out_specs=out_specs,
            check_vma=False,
        )(q1, cache["k"], cache["v"], kn, vn, pos, slot)
    else:
        out, m, s, new_k, new_v = _local_decode(
            q1, cache["k"], cache["v"], kn, vn, slot, 0, scale, pos, s_max, ring
        )
        out = out / jnp.maximum(s[..., None], 1e-30)

    h = cfg.n_heads
    out = out.reshape(b, h, cfg.resolved_head_dim).astype(x.dtype)
    y = jnp.einsum("bhd,hde->be", out, p["wo"].astype(x.dtype))
    if cfg.qkv_bias:
        y = y + p["bo"].astype(x.dtype)
    return y[:, None], {"k": new_k, "v": new_v}
