"""Logical-axis sharding: one rule table maps model axis names -> mesh axes.

Models annotate parameters (via ParamSpec.axes) and activations (via
:func:`constrain`) with *logical* names ('embed', 'mlp', 'heads', 'batch',
'seq_kv', ...). A :class:`ShardingContext` installed around tracing resolves
them to PartitionSpecs for the active mesh. Outside a context every
constraint is a no-op, so models run unmodified on a single CPU device
(smoke tests) and fully sharded under the production mesh (dry-run/train).

Divisibility guard: a logical axis whose dim size does not divide the mapped
mesh-axis size silently falls back to replication for that dim (e.g.
kv_heads=8 over a 16-way 'model' axis). This is what makes one rule table
serve all 10 assigned architectures.
"""
from __future__ import annotations

import contextlib
import threading
from typing import Any, Dict, Mapping, Optional, Sequence, Tuple, Union

import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

MeshAxes = Union[None, str, Tuple[str, ...]]

_ctx = threading.local()


def default_rules(mesh: Mesh) -> Dict[str, MeshAxes]:
    """The production rule table (FSDP x TP x EP (+ pod DP))."""
    has_pod = "pod" in mesh.axis_names
    batch: MeshAxes = ("pod", "data") if has_pod else ("data",)
    return {
        # activations
        "batch": batch,
        "seq": None,
        "seq_sp": "model",    # Megatron-style sequence parallelism between TP
                              # regions: residual-stream activations shard S
                              # over 'model', turning TP all-reduces into
                              # reduce-scatter + all-gather and cutting saved
                              # carries by the TP degree.
        "seq_kv": "model",    # long-context KV caches: sequence-parallel (SP)
        "act_embed": None,
        "act_mlp": "model",
        "act_heads": "model",
        # parameters
        "embed": "data",      # FSDP axis
        "vocab": "model",
        "mlp": "model",
        "heads": "model",
        "kv_heads": "model",
        "head_dim": None,
        "experts": "model",   # EP
        "layers": None,
        "d_inner": "model",   # mamba inner channels
        "state": None,
        "conv_w": None,
        "dt_rank": None,
        "frame": None,
        "patch": None,
        "pos": None,
    }


class ShardingContext:
    def __init__(self, mesh: Mesh, rules: Optional[Mapping[str, MeshAxes]] = None):
        self.mesh = mesh
        self.rules = dict(default_rules(mesh))
        if rules:
            self.rules.update(rules)

    def _axis_size(self, mesh_axes: MeshAxes) -> int:
        if mesh_axes is None:
            return 1
        if isinstance(mesh_axes, str):
            mesh_axes = (mesh_axes,)
        return int(np.prod([self.mesh.shape[a] for a in mesh_axes]))

    def spec_for(self, logical_axes: Sequence[Optional[str]], shape: Optional[Sequence[int]] = None,
                 *, allow_pad: bool = False) -> P:
        """PartitionSpec for logical axes, with divisibility fallback.

        ``allow_pad``: permit uneven (padded) sharding — legal only for
        intermediate values via with_sharding_constraint; pjit argument
        shardings must divide exactly."""
        entries = []
        used: set = set()
        for i, name in enumerate(logical_axes):
            mesh_axes = self.rules.get(name) if name else None
            if mesh_axes is None:
                entries.append(None)
                continue
            axes_t = (mesh_axes,) if isinstance(mesh_axes, str) else tuple(mesh_axes)
            # a mesh axis may appear at most once in a PartitionSpec
            axes_t = tuple(a for a in axes_t if a not in used and a in self.mesh.axis_names)
            if not axes_t:
                entries.append(None)
                continue
            size = int(np.prod([self.mesh.shape[a] for a in axes_t]))
            if shape is not None and shape[i] % size != 0:
                # GSPMD supports uneven sharding via padding: worthwhile when
                # the dim exceeds the mesh axis (e.g. 40 heads over 16 chips
                # pads to 48 — 1.2x waste vs 16x for full replication), not
                # when it's smaller (e.g. 8 kv heads over 16 chips).
                if allow_pad and shape[i] >= size:
                    used.update(axes_t)
                    entries.append(axes_t if len(axes_t) > 1 else axes_t[0])
                else:
                    entries.append(None)
                continue
            used.update(axes_t)
            entries.append(axes_t if len(axes_t) > 1 else axes_t[0])
        # trim trailing Nones (cosmetic)
        return P(*entries)

    def sharding_for(self, logical_axes: Sequence[Optional[str]],
                     shape: Optional[Sequence[int]] = None) -> NamedSharding:
        return NamedSharding(self.mesh, self.spec_for(logical_axes, shape))


def current() -> Optional[ShardingContext]:
    return getattr(_ctx, "ctx", None)


@contextlib.contextmanager
def use_sharding(ctx: Optional[ShardingContext]):
    prev = getattr(_ctx, "ctx", None)
    _ctx.ctx = ctx
    try:
        yield ctx
    finally:
        _ctx.ctx = prev


def constrain(x: jax.Array, *logical_axes: Optional[str]) -> jax.Array:
    """with_sharding_constraint by logical axis names; no-op without context."""
    ctx = current()
    if ctx is None:
        return x
    if len(logical_axes) != x.ndim:
        raise ValueError(f"constrain: {len(logical_axes)} axes for ndim {x.ndim}")
    spec = ctx.spec_for(logical_axes, x.shape, allow_pad=True)
    return jax.lax.with_sharding_constraint(x, NamedSharding(ctx.mesh, spec))


def param_specs(meta_tree: Any, params_or_abstract: Any) -> Any:
    """PartitionSpec pytree for a parameter tree from its ParamMeta tree."""
    ctx = current()

    def leaf(m, p):
        if ctx is None:
            return P()
        return ctx.spec_for(m.axes, p.shape)

    return jax.tree.map(leaf, meta_tree, params_or_abstract)


def shardings_for_tree(meta_tree: Any, params_or_abstract: Any) -> Any:
    ctx = current()
    if ctx is None:
        raise RuntimeError("shardings_for_tree requires an active ShardingContext")

    def leaf(m, p):
        return NamedSharding(ctx.mesh, ctx.spec_for(m.axes, p.shape))

    return jax.tree.map(leaf, meta_tree, params_or_abstract)
