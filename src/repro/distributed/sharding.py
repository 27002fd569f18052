"""Sharding rules: param / batch / cache PartitionSpecs per (arch x shape).

Strategy (baseline; hillclimbed variants live in launch/dryrun options):

* **Tensor parallel** over ``model``: column-parallel in-projections
  (wq/wk/wv/w_gate/w_up/...), row-parallel out-projections (wo/w_down/...).
* **FSDP** over ``data`` (+ ``pod``): the non-TP weight dim is sharded over
  the batch axes; XLA all-gathers per scanned layer and reduce-scatters grads.
* **Expert parallel**: expert-stacked weights sharded on the expert dim over
  ``model`` (matches the shard_map dispatch in models/moe.py).
* **Vocab parallel**: embedding (V, D) -> (model, data); tied logits come out
  vocab-sharded and the cross-entropy's logsumexp/gather reduce over `model`.
* **Decode caches**: batch over batch axes; sequence dim over ``model`` when
  kv_heads < |model| (distributed-softmax decode), else kv-heads over
  ``model``. Uneven dims are allowed (GSPMD pads); shard_map inputs are the
  only place that requires exact divisibility.
"""
from __future__ import annotations

import dataclasses
from typing import Any

import jax
from jax.sharding import PartitionSpec as P

from repro.configs.base import ModelConfig
from repro.distributed.context import DistContext

# leaf-name rule sets (matched on the last string key in the tree path)
_COL = {
    "wq", "wk", "wv", "wg", "wr", "w_gate", "w_up", "tm_w1", "cm_wk",
    "in_proj", "w_dq", "w_uq", "w_dkv", "w_ukv", "x_wq", "x_wk", "x_wv",
    "proj", "dt_proj",
}
_ROW = {"wo", "w_down", "cm_wv", "cm_wr", "ssm_out_proj", "x_proj", "x_wo", "head"}
_BIAS_MODEL = {"bq", "bk", "bv", "b_up"}
_EXPERT_IN = {"we_gate", "we_up"}
_EXPERT_OUT = {"we_down"}


def _tail(rank: int, *axes) -> P:
    """PartitionSpec acting on the trailing ``len(axes)`` dims."""
    axes = list(axes)
    if len(axes) > rank:
        axes = axes[len(axes) - rank:]
    return P(*([None] * (rank - len(axes)) + axes))


def _leaf_spec(name: str, rank: int, dist: DistContext) -> P:
    b = dist.batch_axes if len(dist.batch_axes) > 1 else dist.batch_axes[0]
    m = dist.model_axis
    if name == "embed":
        return _tail(rank, m, b)
    if name == "out_head":
        return _tail(rank, b, m)
    if name == "router":
        return _tail(rank, b, None)
    if name in _EXPERT_IN:
        return _tail(rank, m, b, None)
    if name in _EXPERT_OUT:
        return _tail(rank, m, None, b)
    if name in _COL:
        return _tail(rank, b, m)
    if name in _ROW:
        return _tail(rank, m, b)
    if name in _BIAS_MODEL:
        return _tail(rank, m)
    if name in ("conv_w",):
        return _tail(rank, None, m)
    if name in ("a_log",):
        return _tail(rank, m, None)
    if name in ("d_skip", "dt_bias"):
        return _tail(rank, m)
    return P()  # norms, gates, scalars, small LoRAs: replicated


def _path_leaf_name(path) -> str:
    for entry in reversed(path):
        if isinstance(entry, jax.tree_util.DictKey):
            return str(entry.key)
        if hasattr(entry, "key"):
            return str(entry.key)
    return ""


def param_specs(params_tree, dist: DistContext):
    """PartitionSpec pytree matching ``params_tree`` (abstract or concrete).

    jit in/out shardings require exact divisibility, so placements on dims
    that don't divide the axis size are dropped (e.g. 49155/32001-row
    embeddings, hymba's 25-head projections)."""

    def _axis_size(ax) -> int:
        if isinstance(ax, tuple):
            n = 1
            for a in ax:
                n *= dist.mesh.shape[a]
            return n
        return dist.mesh.shape[ax]

    def spec(path, leaf):
        name = _path_leaf_name(path)
        rank = len(leaf.shape)
        raw = _leaf_spec(name, rank, dist)
        if not dist.enabled:
            return raw
        axes = list(raw) + [None] * (rank - len(tuple(raw)))
        out = []
        for dim, ax in zip(leaf.shape, axes):
            if ax is None:
                out.append(None)
            else:
                out.append(ax if dim % _axis_size(ax) == 0 else None)
        return P(*out)

    return jax.tree_util.tree_map_with_path(spec, params_tree)


# --------------------------------------------------------------------------- #
# batches
# --------------------------------------------------------------------------- #
def batch_specs(cfg: ModelConfig, dist: DistContext, global_batch: int | None = None):
    b = dist.batch_axes if _batch_fits(dist, global_batch) else None
    out = {"tokens": P(b, None), "labels": P(b, None)}
    if cfg.family == "encdec":
        out["frames"] = P(b, None, None)
    if cfg.family == "vlm":
        out["vision"] = P(b, None, None)
    return out


def _batch_fits(dist: DistContext, global_batch: int | None) -> bool:
    if global_batch is None or not dist.enabled:
        return True
    return global_batch % max(dist.dp_size, 1) == 0


def token_specs(dist: DistContext, global_batch: int | None = None):
    b = dist.batch_axes if _batch_fits(dist, global_batch) else None
    return P(b, None)


# --------------------------------------------------------------------------- #
# decode caches
# --------------------------------------------------------------------------- #
def cache_specs(cfg: ModelConfig, cache_tree, dist: DistContext):
    """Spec tree matching ``init_cache``'s structure for each family.

    jit in/out shardings require exact divisibility, so every placement is
    checked against the actual leaf shape and dropped (replicated) if the dim
    does not divide — e.g. whisper's 1500-frame cross cache or rwkv's 40
    heads on a 16-wide model axis.
    """
    b = dist.batch_axes
    m = dist.model_axis
    ep = max(dist.ep_size, 1)
    dp = max(dist.dp_size, 1)
    heads_divisible = cfg.n_kv_heads % ep == 0 and dist.ep_size > 1

    def _axis_size(ax) -> int:
        if isinstance(ax, tuple):
            n = 1
            for a in ax:
                n *= dist.mesh.shape[a]
            return n
        return dist.mesh.shape[ax]

    def _fit(leaf, spec: P) -> P:
        """Drop axis placements whose dim size doesn't divide evenly."""
        axes = list(spec) + [None] * (len(leaf.shape) - len(spec))
        out = []
        for dim, ax in zip(leaf.shape, axes):
            if ax is None:
                out.append(None)
            else:
                out.append(ax if dim % _axis_size(ax) == 0 else None)
        return P(*out)

    from repro.models import tuning

    def spec(path, leaf):
        name = _path_leaf_name(path)
        if name == "len" or len(leaf.shape) == 0:
            return P()
        if tuning.ACTIVE.decode_cache_data_only:
            # batch-only sharding: keeps the per-step dynamic-update-slice
            # local (GSPMD re-gathers model-sharded seq dims on update)
            if cfg.family == "hybrid":
                batch_dim = 0
            elif cfg.family == "vlm" and name in ("k", "v"):
                batch_dim = 2
            else:
                batch_dim = 1
            spec_axes = [None] * len(leaf.shape)
            if leaf.shape[batch_dim] % max(dp, 1) == 0:
                spec_axes[batch_dim] = b
            return P(*spec_axes)
        if cfg.family in ("dense", "moe"):
            # (L, B, S, KV, hd)
            raw = (P(None, b, None, m, None) if heads_divisible
                   else P(None, b, m, None, None))
        elif cfg.family == "mla_moe":
            raw = P(None, b, m, None)            # ckv/krope (L, B, S, r)
        elif cfg.family == "rwkv":
            if name == "wkv":                     # (L, B, H, K, V)
                raw = P(None, b, None, m, None)
            else:                                 # shifts (L, B, 1, D)
                raw = P(None, b, None, m)
        elif cfg.family == "hybrid":
            if name in ("k", "v"):                # (B, size, KV, hd)
                raw = P(b, m, None, None)
            elif name == "conv":                  # (B, K-1, I)
                raw = P(b, None, m)
            elif name == "ssm":                   # (B, I, N)
                raw = P(b, m, None)
            else:
                raw = P()
        elif cfg.family == "encdec":
            raw = P(None, b, m, None, None)       # (L,B,S,H,hd) / (L,B,F,H,hd)
        elif cfg.family == "vlm":
            if name in ("k", "v"):                # (G, P, B, S, KV, hd)
                raw = P(None, None, b, m, None, None)
            else:                                 # xk/xv (G, B, Nv, KV, hd)
                raw = P(None, b, m, None, None)
        else:
            raw = P()
        return _fit(leaf, raw)

    return jax.tree_util.tree_map_with_path(spec, cache_tree)


def named(dist: DistContext, spec_tree):
    """PartitionSpec tree -> NamedSharding tree."""
    return jax.tree.map(lambda s: dist.sharding(s), spec_tree,
                        is_leaf=lambda x: isinstance(x, P))


# --------------------------------------------------------------------------- #
# activation-sharding hook (models.common.set_shard_hook)
# --------------------------------------------------------------------------- #
def make_shard_hook(cfg: ModelConfig, dist: DistContext):
    """Turn models.common.hint(x, kind) calls into sharding constraints.

    Without these, GSPMD's internal propagation is free to replicate
    activations (observed: full-batch score buffers at 256-chip scale).
    """
    if not dist.enabled:
        return None
    b = dist.batch_axes
    m = dist.model_axis
    ep = dist.ep_size
    heads_ok = cfg.n_heads % ep == 0
    kv_ok = cfg.n_kv_heads % ep == 0

    from repro.models import tuning

    def hook(x, kind: str):
        if kind == "act_bsd":
            if tuning.ACTIVE.seq_parallel and x.shape[1] % ep == 0:
                return dist.constraint(x, P(b, m, None))
            return dist.constraint(x, P(b, None, None))
        if kind == "act_bshd":
            spec = P(b, None, m, None) if heads_ok else P(b, m, None, None)
            return dist.constraint(x, spec)
        if kind == "kv_bskd":
            spec = P(b, None, m, None) if kv_ok else P(b, None, None, None)
            return dist.constraint(x, spec)
        if kind == "kv_cache_bskd":
            spec = P(b, None, m, None) if kv_ok else P(b, m, None, None)
            return dist.constraint(x, spec)
        if kind == "logits":
            return dist.constraint(x, P(b, None, m))
        return x

    return hook
