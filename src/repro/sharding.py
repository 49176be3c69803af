"""Logical-axis sharding rules (MaxText-style) for the model substrate.

Model code annotates parameters and activations with *logical* axis names;
a rule table maps those to physical mesh axes at trace time.  The same model
definition then runs on the single-pod ``(data, model)`` mesh, the multi-pod
``(pod, data, model)`` mesh, a tiny test mesh, or a single device (where the
annotations are no-ops).

Divisibility guard: a logical axis whose mapped mesh-axis product does not
divide the tensor dimension is dropped (replicated) — e.g. 8 KV heads on a
16-way ``model`` axis, or smollm's 9 query heads.  This matches how
production frameworks degrade and keeps every (arch x mesh) cell compilable.
"""
from __future__ import annotations

import contextlib
import threading
from typing import Optional, Sequence, Union

import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding
from jax.sharding import PartitionSpec as P

Axes = Union[None, str, tuple]

_state = threading.local()


def _ctx():
    if not hasattr(_state, "mesh"):
        _state.mesh = None
        _state.rules = None
    return _state


def shard_map(f, *, mesh, in_specs, out_specs, check_vma: bool = False):
    """``jax.shard_map`` with the repo's default ``check_vma=False``.  All
    repo call sites go through this wrapper."""
    return jax.shard_map(f, mesh=mesh, in_specs=in_specs,
                         out_specs=out_specs, check_vma=check_vma)


@contextlib.contextmanager
def use_sharding(mesh: Optional[Mesh], rules: Optional[dict]):
    """Activate (mesh, rules) for logical annotations in this thread."""
    st = _ctx()
    prev = (st.mesh, st.rules)
    st.mesh, st.rules = mesh, rules
    try:
        yield
    finally:
        st.mesh, st.rules = prev


def active_mesh() -> Optional[Mesh]:
    return _ctx().mesh


def default_rules(multi_pod: bool = False, *, fsdp: bool = True,
                  seq_shard: bool = False, expert_axis: str = "model",
                  pod_pipeline: bool = False) -> dict:
    """Baseline rule table.

    * DP: batch over (pod, data)
    * FSDP/ZeRO-3: weights' non-TP dim over data (within-pod only, so the
      per-layer all-gathers stay on ICI; cross-pod traffic is just the
      gradient all-reduce)
    * TP: heads / ff / vocab over model
    * EP: experts over ``expert_axis``
    * SP (optional): sequence over data for long-context prefill
    """
    data_axes = ("pod", "data") if (multi_pod and not pod_pipeline) else ("data",)
    return {
        # activations
        "act_batch": data_axes,
        "act_seq": "data" if seq_shard else None,
        # residual-stream [B,S,d] tensors only: setting this to "model"
        # turns the TP boundary all-reduces into reduce-scatter+all-gather
        # pairs (sequence parallelism) without touching head/ff axes
        "act_res_seq": "data" if seq_shard else None,
        "act_embed": None,
        "act_heads": "model",
        "act_kv_heads": "model",
        "act_ff": "model",
        "act_vocab": "model",
        "act_kv_seq": "data" if seq_shard else None,
        "act_cache_batch": data_axes,
        "act_cache_seq": None,
        "act_experts": expert_axis,
        "act_inner": "model",
        # parameters
        "fsdp": "data" if fsdp else None,
        "tp": "model",
        "kv_tp": "model",
        "embed_vocab": "model",
        "experts": expert_axis,
        "stage": "pod" if pod_pipeline else None,
        "none": None,
    }


def _resolve(axes: Sequence[Axes], rules: dict):
    out = []
    for a in axes:
        if a is None:
            out.append(None)
        else:
            r = rules.get(a, None)
            out.append(r)
    return out


def _axis_size(mesh: Mesh, axis) -> int:
    if axis is None:
        return 1
    if isinstance(axis, (tuple, list)):
        return int(np.prod([_axis_size(mesh, a) for a in axis]))
    return mesh.shape[axis]


def spec_for(shape: Sequence[int], axes: Sequence[Axes],
             mesh: Optional[Mesh] = None,
             rules: Optional[dict] = None) -> P:
    """PartitionSpec for ``shape`` under logical ``axes`` with the
    divisibility guard applied per dimension."""
    st = _ctx()
    mesh = mesh if mesh is not None else st.mesh
    rules = rules if rules is not None else st.rules
    if mesh is None or rules is None:
        return P()
    assert len(shape) == len(axes), (shape, axes)
    resolved = _resolve(axes, rules)
    parts = []
    for dim, phys in zip(shape, resolved):
        if phys is None or _axis_size(mesh, phys) <= 1 \
                or dim % _axis_size(mesh, phys) != 0:
            parts.append(None)
        else:
            parts.append(phys)
    return P(*parts)


def constrain(x: jax.Array, *axes: Axes) -> jax.Array:
    """with_sharding_constraint via logical axes (no-op outside a mesh)."""
    st = _ctx()
    if st.mesh is None or st.rules is None:
        return x
    spec = spec_for(x.shape, axes)
    return jax.lax.with_sharding_constraint(
        x, NamedSharding(st.mesh, spec))


def named_sharding(shape, axes, mesh=None, rules=None) -> NamedSharding:
    st = _ctx()
    mesh = mesh if mesh is not None else st.mesh
    return NamedSharding(mesh, spec_for(shape, axes, mesh, rules))
