"""Block stack: heterogeneous layers under a scan-over-groups.

Layers are grouped into ``n_layers / period`` identical *groups*; the layer
kind at position p within a group is the same for every group (period is the
LCM of all interleave periods), so per-position parameters stack along a
leading group axis and the stack is evaluated with one ``lax.scan``. This
keeps HLO size O(period) instead of O(n_layers) — essential for tractable
512-device SPMD compiles and the standard production pattern for deep models.
"""
from __future__ import annotations

import functools
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.models import attention as attn
from repro.models import moe as moe_lib
from repro.models import ssm as ssm_lib
from repro.models import xlstm as xlstm_lib
from repro.models.layers import dtype_of, ffn_apply, ffn_init, rmsnorm, rmsnorm_init


def _pos_name(p: int) -> str:
    return f"pos{p:02d}"


def block_init(key, cfg: ModelConfig, layer_pos: int):
    """Init one block (mixer + optional ffn/moe) for group position p."""
    kind = cfg.layer_kind(layer_pos)
    ks = jax.random.split(key, 2)
    p: Dict[str, Any] = {"mixer_norm": rmsnorm_init(cfg.d_model)}
    if kind == "attn":
        p["mixer"] = attn.attn_init(ks[0], cfg)
    elif kind == "ssm":
        p["mixer"] = ssm_lib.ssm_init(ks[0], cfg)
    elif kind == "mlstm":
        p["mixer"] = xlstm_lib.mlstm_init(ks[0], cfg)
    elif kind == "slstm":
        p["mixer"] = xlstm_lib.slstm_init(ks[0], cfg)
    else:
        raise ValueError(kind)
    if kind in ("attn", "ssm"):
        if cfg.layer_is_moe(layer_pos):
            p["ffn_norm"] = rmsnorm_init(cfg.d_model)
            p["moe"] = moe_lib.moe_init(ks[1], cfg)
        elif cfg.d_ff > 0:
            p["ffn_norm"] = rmsnorm_init(cfg.d_model)
            p["ffn"] = ffn_init(ks[1], cfg.d_model, cfg.d_ff, cfg.act, dtype_of(cfg))
    return p


def stack_init(key, cfg: ModelConfig):
    """Stacked params: {posNN: block_params with leading n_groups dim}."""
    period, n_groups = cfg.resolved_scan_period, cfg.n_groups
    out = {}
    for p in range(period):
        per_group = []
        for g in range(n_groups):
            k = jax.random.fold_in(jax.random.fold_in(key, g), p)
            per_group.append(block_init(k, cfg, p))
        out[_pos_name(p)] = jax.tree_util.tree_map(
            lambda *xs: jnp.stack(xs, axis=0), *per_group)
    out["final_norm"] = rmsnorm_init(cfg.d_model)
    return out


def block_apply(params, x, positions, cfg: ModelConfig, layer_pos: int,
                cache: Optional[Dict] = None, cache_index=None,
                return_state: bool = False, use_pallas: bool = False):
    """Apply one block. Returns (x, new_cache, aux_loss)."""
    kind = cfg.layer_kind(layer_pos)
    aux = jnp.zeros((), jnp.float32)
    h = rmsnorm(params["mixer_norm"], x, cfg.norm_eps)
    new_cache = None
    if kind == "attn":
        out, new_cache = attn.attn_apply(
            params["mixer"], h, positions, cfg, cache=cache,
            cache_index=cache_index, use_pallas=use_pallas)
    elif kind == "ssm":
        out, new_cache = ssm_lib.ssm_apply(
            params["mixer"], h, cfg, state=cache, return_state=return_state,
            use_pallas=use_pallas)
    elif kind == "mlstm":
        out, new_cache = xlstm_lib.mlstm_apply(
            params["mixer"], h, cfg, state=cache, return_state=return_state)
    else:  # slstm
        out, new_cache = xlstm_lib.slstm_apply(
            params["mixer"], h, cfg, state=cache, return_state=return_state,
            use_pallas=use_pallas)
    x = x + out
    if "moe" in params:
        h = rmsnorm(params["ffn_norm"], x, cfg.norm_eps)
        out, aux = moe_lib.moe_apply(params["moe"], h, cfg)
        x = x + out
    elif "ffn" in params:
        h = rmsnorm(params["ffn_norm"], x, cfg.norm_eps)
        x = x + ffn_apply(params["ffn"], h, cfg.act)
    return x, new_cache, aux


def _group_apply(group_params, x, positions, cfg: ModelConfig,
                 group_caches: Optional[Dict], cache_index,
                 return_state: bool, use_pallas: bool):
    """Apply one group (period consecutive blocks). Unrolled inside scan."""
    period = cfg.resolved_scan_period
    new_caches = {}
    aux_total = jnp.zeros((), jnp.float32)
    for p in range(period):
        name = _pos_name(p)
        cache = group_caches.get(name) if group_caches is not None else None
        x, nc, aux = block_apply(
            group_params[name], x, positions, cfg, p, cache=cache,
            cache_index=cache_index, return_state=return_state,
            use_pallas=use_pallas)
        aux_total = aux_total + aux
        if nc is not None:
            new_caches[name] = nc
    return x, new_caches, aux_total


def stack_apply(params, x, positions, cfg: ModelConfig,
                caches: Optional[Dict] = None, cache_index=None,
                return_state: bool = False, use_pallas: bool = False):
    """Run all groups with lax.scan. caches: {posNN: stacked cache pytree}.

    Returns (x, new_caches|None, aux_loss).
    """
    blocks = {k: v for k, v in params.items() if k.startswith("pos")}

    def body(carry, xs):
        x, aux_in = carry
        group_params, group_caches = xs
        x, new_caches, aux = _group_apply(
            group_params, x, positions, cfg, group_caches, cache_index,
            return_state=return_state or caches is not None,
            use_pallas=use_pallas)
        return (x, aux_in + aux), new_caches

    if cfg.remat == "block":
        body = jax.checkpoint(
            body, policy=jax.checkpoint_policies.dots_with_no_batch_dims_saveable)
    elif cfg.remat == "full":
        body = jax.checkpoint(body)

    (x, aux), new_caches = jax.lax.scan(
        body, (x, jnp.zeros((), jnp.float32)), (blocks, caches))
    x = rmsnorm(params["final_norm"], x, cfg.norm_eps)
    if not (return_state or caches is not None):
        new_caches = None
    return x, new_caches, aux


# ---------------------------------------------------------------------------
# Cache construction
# ---------------------------------------------------------------------------

#: the axis of the batch in every leaf of ``init_caches``: each layer's
#: cache puts the batch first, and the group axis is stacked before it
BATCH_AXIS = 1


def _one_cache(cfg: ModelConfig, layer_pos: int, batch: int, max_len: int,
               spec: bool):
    kind = cfg.layer_kind(layer_pos)
    if kind == "attn":
        return (attn.cache_spec if spec else attn.init_cache)(cfg, batch, max_len)
    if kind == "ssm":
        return (ssm_lib.ssm_state_spec if spec else ssm_lib.init_ssm_state)(cfg, batch)
    if kind in ("mlstm", "slstm"):
        if spec:
            return xlstm_lib.xlstm_state_spec(cfg, batch, kind)
        return xlstm_lib.init_xlstm_state(cfg, batch, kind)
    raise ValueError(kind)


def cache_layout(cfg: ModelConfig):
    """The kind of each leaf of ``init_caches(cfg, ...)``, in the same
    tree: "kv" (keys and values by position) for an attention layer's
    cache, "state" (recurrent) for any other layer's."""
    out = {}
    for p in range(cfg.resolved_scan_period):
        kind = "kv" if cfg.layer_kind(p) == "attn" else "state"
        out[_pos_name(p)] = jax.tree_util.tree_map(
            lambda _: kind, _one_cache(cfg, p, 1, 1, spec=True))
    return out


def init_caches(cfg: ModelConfig, batch: int, max_len: int, spec: bool = False):
    """Stacked caches {posNN: leading n_groups dim}, matching stack_apply."""
    period, n_groups = cfg.resolved_scan_period, cfg.n_groups
    out = {}
    for p in range(period):
        one = _one_cache(cfg, p, batch, max_len, spec)
        if spec:
            out[_pos_name(p)] = jax.tree_util.tree_map(
                lambda s: jax.ShapeDtypeStruct((n_groups,) + s.shape, s.dtype), one)
        else:
            out[_pos_name(p)] = jax.tree_util.tree_map(
                lambda a: jnp.broadcast_to(a[None], (n_groups,) + a.shape), one)
    return out
