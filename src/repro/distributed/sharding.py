"""Per-architecture parameter / activation / cache sharding rules.

Strategy (MaxText-style 2D sharding):
  * tensor parallelism on ``model``: attention head projections, FFN hidden,
    vocab, MoE experts (expert parallelism), Mamba heads;
  * FSDP on ``data`` (+ ``pod`` on the multi-pod mesh): the non-TP dim of
    every large matrix is additionally sharded, so optimizer state and
    weights fit; XLA inserts the per-layer all-gathers;
  * activations: batch on (pod, data); heads/ffn/vocab/experts on model;
  * decode caches: batch on (pod, data) when divisible, cache sequence on
    model otherwise (long_500k with batch 1 shards S over (data, model)).

Every rule is divisibility-checked against the mesh and silently dropped
when a dim does not divide — the dry-run must lower for every (arch, shape)
including kv_heads=2 and batch=1 cases.
"""
from __future__ import annotations

import math
from typing import Any, Dict, Optional, Sequence, Tuple

import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P


def axis_size(mesh: Mesh, axes) -> int:
    if axes is None:
        return 1
    sizes = dict(zip(mesh.axis_names, mesh.devices.shape, strict=True))
    if isinstance(axes, str):
        return sizes[axes]
    return int(np.prod([sizes[a] for a in axes]))


def _fit(mesh: Mesh, dim: int, axes):
    """Return axes if dim divides their product, else None."""
    if axes is None:
        return None
    return axes if dim % axis_size(mesh, axes) == 0 else None


def data_axes(mesh: Mesh):
    return ("pod", "data") if "pod" in mesh.axis_names else "data"


# ---------------------------------------------------------------------------
# Parameter specs
# ---------------------------------------------------------------------------
_COL_PARALLEL = {"wq", "wk", "wv", "wi_gate", "wi_up", "w1", "w_uk", "w_uv",
                 "in_proj", "frontend_proj", "vision_proj", "lm_head"}
_ROW_PARALLEL = {"wo", "w2", "out_proj"}


def _param_spec(mesh: Mesh, path: Tuple[str, ...], shape: Tuple[int, ...]
                ) -> P:
    name = path[-1]
    in_moe_experts = ("moe" in path and "shared" not in path
                      and name in ("wi_gate", "wi_up", "wo"))
    fsdp = data_axes(mesh)

    if len(shape) == 0 or min(shape) == 0:
        return P()

    def pad(tail: Sequence) -> P:
        """Left-pad with None for stacked layer dims."""
        lead = len(shape) - len(tail)
        return P(*([None] * lead + list(tail)))

    if in_moe_experts:
        # (E, d, f) or (E, f, d): experts on model, fsdp on the larger inner dim
        e, a, b = shape[-3], shape[-2], shape[-1]
        return pad([_fit(mesh, e, "model"),
                    _fit(mesh, a, fsdp), None])
    if name == "router":
        return pad([_fit(mesh, shape[-2], fsdp), None])
    if name == "embed":
        # vocab rows FSDP-sharded, hidden on model: the token gather's
        # output takes its batch sharding from the tokens (on ``fsdp``), so
        # the hidden dim must not sit on ``fsdp`` too
        return P(_fit(mesh, shape[0], fsdp), _fit(mesh, shape[1], "model"))
    if name in _COL_PARALLEL and len(shape) >= 2:
        return pad([_fit(mesh, shape[-2], fsdp),
                    _fit(mesh, shape[-1], "model")])
    if name in _ROW_PARALLEL and len(shape) >= 2:
        return pad([_fit(mesh, shape[-2], "model"),
                    _fit(mesh, shape[-1], fsdp)])
    if name == "w_dkv" and len(shape) >= 2:   # MLA down-proj: small, fsdp only
        return pad([_fit(mesh, shape[-2], fsdp), None])
    if name == "conv_w":
        return pad([None, _fit(mesh, shape[-1], "model")])
    # scales, biases, A_log, D, dt_bias, kv_norm ... replicated
    return P(*([None] * len(shape)))


def param_specs(mesh: Mesh, params_shapes) -> Any:
    """Map a pytree of ShapeDtypeStruct/arrays to PartitionSpecs."""
    flat, treedef = jax.tree_util.tree_flatten_with_path(params_shapes)
    specs = []
    for path, leaf in flat:
        names = tuple(_key_name(p) for p in path)
        specs.append(_param_spec(mesh, names, tuple(leaf.shape)))
    return jax.tree_util.tree_unflatten(treedef, specs)


def _key_name(p) -> str:
    if hasattr(p, "key"):
        return str(p.key)
    if hasattr(p, "idx"):
        return str(p.idx)
    return str(p)


# ---------------------------------------------------------------------------
# Batch / cache specs
# ---------------------------------------------------------------------------
def batch_specs(mesh: Mesh, batch_shapes: Dict[str, Any]) -> Dict[str, P]:
    da = data_axes(mesh)
    out = {}
    for k, v in batch_shapes.items():
        shape = tuple(v.shape)
        if k == "positions_3d":            # (3, b, s)
            out[k] = P(None, _fit(mesh, shape[1], da), None)
        else:                               # (b, ...) leading batch
            out[k] = P(*( [_fit(mesh, shape[0], da)]
                          + [None] * (len(shape) - 1)))
    return out


def cache_specs(mesh: Mesh, cache_shapes) -> Any:
    """Decode-cache specs: (layer-stack, batch, ...) leaves."""
    da = data_axes(mesh)
    flat, treedef = jax.tree_util.tree_flatten_with_path(cache_shapes)
    specs = []
    for path, leaf in flat:
        name = _key_name(path[-1])
        shape = tuple(leaf.shape)
        spec = [None] * len(shape)
        if len(shape) >= 2:
            spec[1] = _fit(mesh, shape[1], da)          # batch dim
        if name in ("k", "v", "ck", "cv") and len(shape) == 5:
            # (L, b, h, S, hd): heads on model if divisible, else seq
            h_ax = _fit(mesh, shape[2], "model")
            if h_ax is not None:
                spec[2] = h_ax
            else:
                spec[3] = _fit(mesh, shape[3], "model")
            if spec[1] is None and spec[3] is None:
                # batch unshardable (b=1): spread sequence over everything
                spec[3] = _fit(mesh, shape[3],
                               (da, "model") if isinstance(da, str)
                               else tuple(da) + ("model",))
                if spec[3] is not None:
                    spec[2] = None
        elif name in ("c_kv", "k_rope") and len(shape) == 4:
            # (L, b, S, dim): sequence on model
            spec[2] = _fit(mesh, shape[2], "model")
            if spec[1] is None and spec[2] is not None:
                full = (da, "model") if isinstance(da, str) else tuple(da) + ("model",)
                alt = _fit(mesh, shape[2], full)
                if alt is not None:
                    spec[2] = alt
        elif name == "ssm" and len(shape) == 5:
            spec[2] = _fit(mesh, shape[2], "model")     # heads
        elif name == "conv" and len(shape) == 4:
            spec[3] = _fit(mesh, shape[3], "model")     # channels
        specs.append(P(*spec))
    return jax.tree_util.tree_unflatten(treedef, specs)


# ---------------------------------------------------------------------------
# Activation rules for models.common.activation_mesh
# ---------------------------------------------------------------------------
def activation_rules(mesh: Mesh) -> Dict[str, Any]:
    da = data_axes(mesh)
    return {"batch": da, "heads": "model", "ffn": "model",
            "vocab": "model", "expert": "model", "residual": "model"}
