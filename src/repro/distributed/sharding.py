"""Deterministic sharding planner: TP + FSDP(+pod-DP) PartitionSpecs for any
param tree, divisibility-safe per architecture.

Axis roles on the production mesh (see launch/mesh.py):
  - 'model'          : tensor parallelism (Megatron column/row split)
  - 'data' (+ 'pod') : data parallelism for activations AND FSDP sharding of
                       params/optimizer state (ZeRO-3 via GSPMD: params carry
                       a data-axis dim in their spec; XLA inserts the
                       per-layer all-gather in fwd and reduce-scatter in bwd)

Rules are path-pattern based (Megatron conventions: column-parallel in
wq/wk/wv/wi/wg, row-parallel in wo), with a generic fallback; every axis
assignment is divisibility-checked against the actual dim and dropped when
it does not divide (e.g. mixtral's 8 experts never shard over a 16-way axis,
llama3.2's 24 q-heads are shared via the flattened 3072 dim instead).
"""
from __future__ import annotations

import dataclasses
import re
from typing import Any, Dict, List, Optional, Sequence, Tuple

import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

PyTree = Any


@dataclasses.dataclass(frozen=True)
class MeshSpec:
    """Axis-role view of a mesh."""

    mesh: Mesh
    tp_axis: str = "model"
    dp_axes: Tuple[str, ...] = ("data",)      # includes 'pod' when present
    sequence_parallel: bool = False

    @classmethod
    def from_mesh(cls, mesh: Mesh, sequence_parallel: bool = False) -> "MeshSpec":
        names = mesh.axis_names
        dp = tuple(a for a in ("pod", "data") if a in names)
        return cls(mesh=mesh, tp_axis="model" if "model" in names else names[-1],
                   dp_axes=dp, sequence_parallel=sequence_parallel)

    @property
    def tp_size(self) -> int:
        return self.mesh.shape[self.tp_axis]

    @property
    def dp_size(self) -> int:
        return int(np.prod([self.mesh.shape[a] for a in self.dp_axes]))

    def dp_spec_for(self, dim: int) -> Optional[Tuple[str, ...]]:
        """Largest prefix-product combination of dp axes that divides dim."""
        # try full ('pod','data'), then single axes largest-first
        candidates: List[Tuple[str, ...]] = []
        if len(self.dp_axes) > 1:
            candidates.append(tuple(self.dp_axes))
        candidates.extend((a,) for a in sorted(
            self.dp_axes, key=lambda a: -self.mesh.shape[a]))
        for cand in candidates:
            size = int(np.prod([self.mesh.shape[a] for a in cand]))
            if dim % size == 0:
                return cand
        return None


# Param rules: (path regex, spec template applied to trailing dims).
# Template entries: 'tp', 'fsdp', None.  A leading layer-stack dim (when leaf
# ndim exceeds the template length) is always unsharded.
_PARAM_RULES: Sequence[Tuple[str, Tuple[Optional[str], ...]]] = (
    (r"embed/embedding$", ("tp", "fsdp")),          # (V, D) vocab-parallel
    (r"embed/unembed$", ("fsdp", "tp")),            # (D, V)
    (r"attn/w[qkv]$", ("fsdp", "tp")),              # column-parallel
    (r"attn/wo$", ("tp", "fsdp")),                  # row-parallel
    (r"attn/b[qkv]$", ("tp",)),
    (r"(ffn|mlp)/w[ig]$", ("fsdp", "tp")),
    (r"(ffn|mlp)/wo$", ("tp", "fsdp")),
    (r"ffn/w[kv]$", ("fsdp", "tp")),                # rwkv channel-mix
    (r"moe/router$", ("fsdp", None)),               # (D, E): E stays whole
    (r"moe/w[ig]$", ("exp", "fsdp", "tp")),         # (E, D, F)
    (r"moe/wo$", ("exp", "tp", "fsdp")),            # (E, F, D)
    (r"rwkv/w[rkvgo]$", ("fsdp", "tp")),
    (r"rwkv/(mix_lora_a|decay_lora_a)$", ("fsdp", None)),
    (r"rwkv/mix_lora_b$", (None, None, "tp")),
    (r"rwkv/decay_lora_b$", (None, "tp")),
    (r"rwkv/bonus$", (None, None)),
    (r"mamba/in_proj$", ("fsdp", "tp")),
    (r"mamba/out_proj$", ("tp", "fsdp")),
    (r"mamba/conv$", (None, "tp")),
)


def _path_str(path) -> str:
    parts = []
    for p in path:
        if isinstance(p, jax.tree_util.DictKey):
            parts.append(str(p.key))
        elif isinstance(p, jax.tree_util.SequenceKey):
            parts.append(str(p.idx))
        else:
            parts.append(str(p))
    return "/".join(parts)


def _assign(template: Tuple[Optional[str], ...], shape: Tuple[int, ...],
            spec: MeshSpec, n_layers_hint: int) -> P:
    ndim = len(shape)
    # right-align the template; leading (layer-stack) dims unsharded
    lead = ndim - len(template)
    entries: List[Any] = [None] * ndim
    used_exp_axes: Tuple[str, ...] = ()
    for i, role in enumerate(template):
        dim = shape[lead + i]
        if role == "tp":
            if dim % spec.tp_size == 0:
                entries[lead + i] = spec.tp_axis
        elif role == "exp":
            # expert dim: shard over dp axes when divisible (expert parallel)
            axes = spec.dp_spec_for(dim)
            if axes:
                entries[lead + i] = axes if len(axes) > 1 else axes[0]
                used_exp_axes = axes
        elif role == "fsdp":
            axes = tuple(a for a in spec.dp_axes if a not in used_exp_axes)
            if axes:
                size = int(np.prod([spec.mesh.shape[a] for a in axes]))
                if dim % size == 0:
                    entries[lead + i] = axes if len(axes) > 1 else axes[0]
                else:  # fall back to single largest dividing axis
                    for a in sorted(axes, key=lambda a: -spec.mesh.shape[a]):
                        if dim % spec.mesh.shape[a] == 0:
                            entries[lead + i] = a
                            break
    return P(*entries)


def _generic_spec(shape: Tuple[int, ...], spec: MeshSpec,
                  n_layers_hint: int) -> P:
    """Fallback: TP on the last divisible of the trailing two dims, FSDP on
    the largest remaining divisible dim.  Vectors replicate."""
    ndim = len(shape)
    if ndim <= 1 or max(shape) < 128:
        return P()
    entries: List[Any] = [None] * ndim
    start = 1 if (ndim >= 3 and shape[0] == n_layers_hint) else 0
    for i in (ndim - 1, ndim - 2):
        if i >= start and shape[i] % spec.tp_size == 0:
            entries[i] = spec.tp_axis
            break
    remaining = [i for i in range(start, ndim) if entries[i] is None]
    for i in sorted(remaining, key=lambda i: -shape[i]):
        axes = spec.dp_spec_for(shape[i])
        if axes:
            entries[i] = axes if len(axes) > 1 else axes[0]
            break
    return P(*entries)


def plan_params(params_shape: PyTree, spec: MeshSpec,
                n_layers_hint: int = -1) -> PyTree:
    """PartitionSpec tree for a param tree (of ShapeDtypeStructs or arrays)."""

    def leaf_spec(path, leaf) -> P:
        pstr = _path_str(path)
        shape = tuple(leaf.shape)
        for pattern, template in _PARAM_RULES:
            if re.search(pattern, pstr):
                if len(shape) < len(template):
                    # unstacked variant (e.g. shared block, no L dim)
                    template = template[len(template) - len(shape):]
                return _assign(template, shape, spec, n_layers_hint)
        return _generic_spec(shape, spec, n_layers_hint)

    return jax.tree_util.tree_map_with_path(leaf_spec, params_shape)


def plan_batch(batch_shape: PyTree, spec: MeshSpec) -> PyTree:
    """Batch arrays: shard the leading (batch) dim over dp axes."""

    def leaf_spec(leaf) -> P:
        shape = tuple(leaf.shape)
        if not shape:
            return P()
        axes = spec.dp_spec_for(shape[0])
        if axes is None:
            return P()
        first = axes if len(axes) > 1 else axes[0]
        return P(first, *([None] * (len(shape) - 1)))

    return jax.tree.map(leaf_spec, batch_shape)


def plan_decode_state(state_shape: PyTree, spec: MeshSpec,
                      n_layers_hint: int = -1,
                      attn_kv_shard: str = "head") -> PyTree:
    """Cache/state trees: dp on batch dim, tp on a trailing divisible dim.

    Leaves look like (L, B, S, KV, HD) / (L, B, H, K, V) / (L, B, W, C);
    the batch dim is index 1 when a leading layer-stack dim is present.

    ``attn_kv_shard``:
      'head': shard the KV cache on head_dim (naive; the attention einsum
        contracts head_dim, which forces the SPMD partitioner into a
        full-cache replication per layer — see EXPERIMENTS.md §Perf C-cell)
      'seq': shard the KV cache along the sequence dim over the tp axis —
        scores are computed shard-locally, softmax reduces with a small
        all-reduce, and the cache is never re-materialized.
    """

    def leaf_spec(path, leaf) -> P:
        pstr = _path_str(path)
        shape = tuple(leaf.shape)
        if not shape or leaf.dtype == np.int32 and not shape:
            return P()
        if len(shape) <= 1:
            return P()
        entries: List[Any] = [None] * len(shape)
        b_idx = 1 if len(shape) >= 3 else 0
        axes = spec.dp_spec_for(shape[b_idx])
        if axes:
            entries[b_idx] = axes if len(axes) > 1 else axes[0]
        is_attn_kv = re.search(r"(^|/)(k|v)$", pstr) and len(shape) >= 4
        if is_attn_kv and attn_kv_shard == "seq":
            s_idx = b_idx + 1                      # (L, B, S, KV, HD)
            if shape[s_idx] % spec.tp_size == 0:
                entries[s_idx] = spec.tp_axis
                return P(*entries)
        # tp on the last trailing dim (after batch) that divides; prefer
        # later dims (head_dim / channels)
        for i in range(len(shape) - 1, b_idx, -1):
            if shape[i] % spec.tp_size == 0:
                entries[i] = spec.tp_axis
                break
        return P(*entries)

    return jax.tree_util.tree_map_with_path(leaf_spec, state_shape)


# ---------------------------------------------------------------------------
# Activation constraint factory (the model's shard_fn)
# ---------------------------------------------------------------------------

def make_shard_fn(spec: MeshSpec):
    """Returns shard_fn(tag, x) applying with_sharding_constraint by tag."""
    dp = spec.dp_axes if len(spec.dp_axes) > 1 else (
        spec.dp_axes[0] if spec.dp_axes else None)

    def shard_fn(tag: str, x):
        if x.ndim == 3:
            if tag == "logits":
                s = P(dp, None, spec.tp_axis if x.shape[-1] % spec.tp_size == 0 else None)
            elif spec.sequence_parallel and tag in ("activation", "residual") \
                    and x.shape[1] % spec.tp_size == 0:
                s = P(dp, spec.tp_axis, None)
            else:
                s = P(dp, None, None)
        elif x.ndim == 2:
            s = P(dp, None)
        else:
            return x
        # drop dp if batch not divisible (e.g. long_500k batch=1)
        if dp is not None and s[0] is not None:
            dp_size = spec.dp_size if isinstance(dp, tuple) else spec.mesh.shape[dp]
            if x.shape[0] % dp_size != 0:
                s = P(None, *s[1:])
        return jax.lax.with_sharding_constraint(x, NamedSharding(spec.mesh, s))

    return shard_fn


def named(spec: MeshSpec, pspec_tree: PyTree) -> PyTree:
    return jax.tree.map(lambda s: NamedSharding(spec.mesh, s), pspec_tree,
                        is_leaf=lambda x: isinstance(x, P))


def strip_dp_axes(pspec_tree: PyTree, spec: MeshSpec) -> PyTree:
    """Remove dp (FSDP) axes from every PartitionSpec — TP-only layout.

    Serving wants this: FSDP params would be all-gathered on EVERY decode
    step; TP-only replicates each shard across the data axis once."""
    dp = set(spec.dp_axes)

    def strip(s: P) -> P:
        entries = []
        for e in tuple(s):
            if e is None:
                entries.append(None)
            elif isinstance(e, tuple):
                kept = tuple(a for a in e if a not in dp)
                entries.append(kept if len(kept) > 1 else (kept[0] if kept else None))
            else:
                entries.append(None if e in dp else e)
        return P(*entries)

    return jax.tree.map(strip, pspec_tree, is_leaf=lambda x: isinstance(x, P))
