"""Layer-wise SNR analysis of Adam's second moments (paper Eq. 3-4).

For a second-moment tensor V and compression dims K:

    SNR_K(V) = E_{K'}[ (E_K[V])^2 / Var_K[V] ]

where the inner mean/variance run over K and the outer expectation averages
the ratio over every remaining dim K'. ``SNR_K >~ 1`` means the entries along
K are well represented by their mean -> compressible.

This module is pure-jnp and jit-safe; :class:`SNRTracker` accumulates the
paper's time-averaged SNR (Eq. 4) across measurement steps.
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Any, Dict, Mapping, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp

from ..optim.base import resolve_backend
from .labels import ParamMeta, flatten_with_names

_VAR_EPS = 1e-30  # guards 0/0 for exactly-constant slices; SNR -> huge (compressible)


def snr_along_dims(v: jnp.ndarray, dims: Tuple[int, ...], *, per_remaining_dim: Optional[int] = None,
                   backend: str = "jnp", mesh=None, spec=None) -> jnp.ndarray:
    """SNR_K for positional reduction dims.

    Returns a scalar, or — when ``per_remaining_dim`` names a remaining dim —
    a vector over that dim (used for per-depth curves on scan-stacked params).

    ``backend='fused'`` computes the scalar form through the fused snr_stats
    kernel: one pass over V yields per-row (sum, sum-sq) jointly, so the
    measurement adds a single read of V instead of XLA's separate mean and
    variance reductions. The per-remaining-dim form always runs in jnp.

    ``mesh`` + ``spec`` (the moment's PartitionSpec) run the scalar form
    under ``shard_map`` so the measurement is correct for a sharded V
    instead of silently per-host: reduction lines whole on every shard are
    measured locally and the per-line ratios averaged with a ``lax.pmean``;
    reduction lines *split* across shards compute per-shard partial centered
    stats (the kernels' partial-sums entry point off the fused backend, jnp
    otherwise), rebase them to a mesh-common shift, and ``lax.psum`` before
    the ratio — the one-pass centered-variance trick composes across the
    shard boundary (see ``repro.kernels.ref.rebase_centered_stats``).
    """
    if not dims:
        raise ValueError("K must be non-empty for SNR; K=None means 'no compression'")
    if mesh is not None and spec is not None:
        from ..sharding.shardspec import mesh_is_trivial

        if not mesh_is_trivial(mesh):
            if per_remaining_dim is not None:
                raise ValueError("per-remaining-dim SNR curves are single-device "
                                 "only; pass mesh=None for per-depth reporting")
            return _sharded_snr(v, tuple(dims), spec, mesh, backend)
    if resolve_backend(backend) == "fused" and per_remaining_dim is None:
        # snr_op is the jit-cached centered-stats kernel + finalization (its
        # eps equals _VAR_EPS); only the canonicalization happens here.
        from ..kernels.ops import canon_apply, default_interpret, leaf_plan, snr_op
        from ..kernels.snr_stats import CENTERED_BUFS
        # leaf_plan names whichever batched (B, R, C) layout a pure reshape
        # reaches — trailing K (minor), leading K (major), or a scan-stacked
        # kept/K/kept pattern (batched major) — and gates on VMEM. It routes
        # to jnp when the plan would transpose (an interleaved K would
        # materialize a full re-layout of V across the kernel boundary, ~3x
        # the single read this path promises) or the reduction line can't be
        # strip-tiled at all.
        plan = leaf_plan(v.shape, v.dtype, dims, n_bufs=CENTERED_BUFS,
                         allow_transpose=False)
        if plan.route == "slim":
            v2 = canon_apply(v.astype(jnp.float32), plan.cn)
            return snr_op(v2, axis=plan.cn.axis, interpret=default_interpret())
    v = v.astype(jnp.float32)
    mean = jnp.mean(v, axis=dims, keepdims=True)
    var = jnp.mean(jnp.square(v - mean), axis=dims, keepdims=True)
    ratio = jnp.square(mean) / (var + _VAR_EPS)
    ratio = jnp.squeeze(ratio, axis=dims)
    if per_remaining_dim is None:
        return jnp.mean(ratio)
    # Map the original dim index to its index after squeezing K dims.
    kept = [d for d in range(v.ndim) if d not in dims]
    if per_remaining_dim not in kept:
        raise ValueError(f"dim {per_remaining_dim} was reduced by K={dims}")
    axis_after = kept.index(per_remaining_dim)
    other = tuple(i for i in range(ratio.ndim) if i != axis_after)
    return jnp.mean(ratio, axis=other)


def _psum_line_snr(v_loc: jnp.ndarray, dims: Tuple[int, ...], axes: Tuple[str, ...],
                   red_total: int, backend: str) -> jnp.ndarray:
    """Per-shard body for reduction lines split across ``axes``: partial
    centered stats (kernel or jnp), rebase to a mesh-common shift, psum,
    finalize. Returns the local mean of the completed per-line ratios."""
    from ..kernels.ref import rebase_centered_stats, snr_from_centered_stats, \
        snr_stats_centered_partial_ref

    v32 = v_loc.astype(jnp.float32)
    dset = {d % v32.ndim for d in dims}
    n_loc = 1
    for d in sorted(dset):
        n_loc *= v32.shape[d]
    s1 = s1c = s2c = first = None
    if resolve_backend(backend) == "fused":
        from ..kernels.ops import canon_apply, default_interpret, leaf_plan, snr_partial_op
        from ..kernels.snr_stats import CENTERED_BUFS

        plan = leaf_plan(v32.shape, v32.dtype, tuple(sorted(dset)),
                         n_bufs=CENTERED_BUFS, allow_transpose=False)
        if plan.route == "slim":
            v2 = canon_apply(v32, plan.cn)
            s1, s1c, s2c, first = snr_partial_op(v2, axis=plan.cn.axis,
                                                 interpret=default_interpret())
    if s1 is None:
        s1, s1c, s2c, first = snr_stats_centered_partial_ref(v32, tuple(sorted(dset)))
    # Rebase every shard's centered sums to one common shift before adding
    # them: variance is shift-invariant, but the sums are not.
    shift = jax.lax.pmean(first, axes)
    s1c, s2c = rebase_centered_stats(s1c, s2c, first, shift, n_loc)
    s1 = jax.lax.psum(s1, axes)
    s1c = jax.lax.psum(s1c, axes)
    s2c = jax.lax.psum(s2c, axes)
    return snr_from_centered_stats(s1, s1c, s2c, red_total, eps=_VAR_EPS)


@functools.lru_cache(maxsize=512)
def _sharded_snr_exec(shape: Tuple[int, ...], dtype, dims: Tuple[int, ...], spec,
                      mesh, backend: str):
    """Build (and cache) the jitted shard_map executable for one
    (shape, dtype, dims, spec, mesh, backend) signature. The trainer's
    periodic SNR pass hits the same signatures every measurement step, so
    without this cache each leaf x candidate-K would re-trace a fresh
    shard_map and run its pmean/rebase/psum epilogue op-by-op (the
    single-device path gets the same amortization from the jit-cached
    ``snr_op``)."""
    from jax.sharding import PartitionSpec as P

    from ..sharding.shardspec import even_spec, owning_axes

    ndim = len(shape)
    dset = {d % ndim for d in dims}
    kept = tuple(i for i in range(ndim) if i not in dset)
    spec_e = even_spec(shape, spec, mesh)
    red_axes = owning_axes(shape, spec, mesh, tuple(sorted(dset)))
    kept_axes = owning_axes(shape, spec, mesh, kept)
    red_total = 1
    for d in sorted(dset):
        red_total *= shape[d]

    def local_fn(v_loc):
        if red_axes:
            s = _psum_line_snr(v_loc, tuple(sorted(dset)), red_axes, red_total, backend)
        else:
            s = snr_along_dims(v_loc, tuple(sorted(dset)), backend=backend)
        # Each shard holds an equal slice of the kept lines, so the global
        # ratio mean is the mean of the per-shard means.
        if kept_axes:
            s = jax.lax.pmean(s, kept_axes)
        return s

    return jax.jit(jax.shard_map(local_fn, mesh=mesh, in_specs=(spec_e,),
                                 out_specs=P(), check_vma=False))


def _sharded_snr(v: jnp.ndarray, dims: Tuple[int, ...], spec, mesh, backend: str) -> jnp.ndarray:
    """Scalar SNR_K of a sharded moment via shard_map (see
    :func:`snr_along_dims`). The returned scalar is replicated."""
    ndim = v.ndim
    dset = {d % ndim for d in dims}
    if any(not -ndim <= d < ndim for d in dims) or len(dset) != len(dims):
        raise ValueError(f"bad reduction dims {dims} for shape {v.shape}")
    fn = _sharded_snr_exec(tuple(int(s) for s in v.shape), v.dtype,
                           tuple(sorted(dset)), spec, mesh, backend)
    out = fn(v)
    # Serialize the per-leaf executions: with the jit cache warm, successive
    # leaves' collective programs would otherwise dispatch asynchronously and
    # overlap, which can deadlock XLA's CPU all-reduce rendezvous (distinct
    # executables racing on overlapping device sets). The measurement pass is
    # off the hot path, so blocking per leaf costs nothing that matters.
    if not isinstance(out, jax.core.Tracer):
        out = jax.block_until_ready(out)
    return out


def measure_leaf_snr(v: jnp.ndarray, meta: ParamMeta, *, backend: str = "jnp",
                     mesh=None, spec=None) -> Dict[str, jnp.ndarray]:
    """Scalar SNR per candidate K ('fan_in'/'fan_out'/'both') for one tensor."""
    out: Dict[str, jnp.ndarray] = {}
    for label, axis_names in meta.candidate_ks().items():
        dims = meta.dims_of(axis_names)
        out[label] = snr_along_dims(v, dims, backend=backend, mesh=mesh, spec=spec)
    return out


def measure_leaf_snr_per_layer(v: jnp.ndarray, meta: ParamMeta) -> Dict[str, jnp.ndarray]:
    """Per-depth SNR vectors for scan-stacked tensors (axis 'layers')."""
    if "layers" not in meta.axes:
        return measure_leaf_snr(v, meta)
    layer_dim = meta.axes.index("layers")
    out: Dict[str, jnp.ndarray] = {}
    for label, axis_names in meta.candidate_ks().items():
        dims = meta.dims_of(axis_names)
        out[label] = snr_along_dims(v, dims, per_remaining_dim=layer_dim)
    return out


def measure_tree_snr(nu: Any, meta: Any, *, backend: str = "jnp",
                     mesh=None, param_specs=None, from_update: Any = None,
                     update_dims: Any = None) -> Dict[str, Dict[str, jnp.ndarray]]:
    """{param_name: {K_label: snr}} over a whole second-moment pytree.

    Leaves whose meta marks them vector-like produce an empty dict (the paper
    never compresses them). ``backend='fused'`` runs each candidate's
    mean/var through the one-pass snr_stats kernel.

    ``mesh`` + ``param_specs`` (PartitionSpec pytree mirroring the moment
    tree) measure each leaf under ``shard_map`` so SNR trajectories stay
    correct when the moments live sharded on an FSDP x TP mesh — candidate
    Ks whose dims are split across devices psum their centered stats instead
    of silently measuring per-shard slices.

    ``from_update`` + ``update_dims`` consume SNR scalars that rode the
    optimizer's update pass (``scale_by_slim_adam(emit_snr=True)`` publishes
    them on ``state.snr``; ``update_dims`` is the optimizer's per-leaf
    reduction-dims pytree): for each leaf, the candidate K whose dims equal
    the leaf's update K takes the ridden value — no nu read at all for that
    candidate — and only the remaining candidates fall back to the standard
    measurement. For a SlimAdam run this removes the measure step's extra
    pass over every compressed leaf; K = () leaves (dense-stored moments)
    always use the standard path.
    """
    nu_named, nu_def = flatten_with_names(nu)
    meta_named, _ = flatten_with_names(meta)
    spec_leaves: Any = [None] * len(nu_named)
    if mesh is not None or param_specs is not None:
        from ..sharding.shardspec import normalize_spec_leaves, sharded_pair

        mesh, param_specs = sharded_pair(mesh, param_specs, "measure_tree_snr")
        if mesh is not None:
            spec_leaves = normalize_spec_leaves(param_specs, nu_def,
                                                "measure_tree_snr")
    ridden: Dict[str, Tuple[Any, Tuple[int, ...]]] = {}
    if from_update is not None:
        if update_dims is None:
            raise ValueError("measure_tree_snr: from_update needs update_dims "
                             "(the optimizer's per-leaf reduction-dims pytree)")
        from .labels import path_str

        def named(tree, is_leaf):
            kv = jax.tree_util.tree_flatten_with_path(tree, is_leaf=is_leaf)[0]
            return [(path_str(p), v) for p, v in kv]

        dims_by_name = dict(named(update_dims, lambda x: isinstance(x, tuple)))
        for name, s in named(from_update, lambda x: x is None):
            if s is not None and name in dims_by_name:
                ridden[name] = (s, tuple(dims_by_name[name]))
    out: Dict[str, Dict[str, jnp.ndarray]] = {}
    for (name, v), (_, m), spec in zip(nu_named, meta_named, spec_leaves):
        if name in ridden:
            s_val, s_dims = ridden[name]
            leaf_out: Dict[str, jnp.ndarray] = {}
            for label, axis_names in m.candidate_ks().items():
                dims = tuple(m.dims_of(axis_names))
                if tuple(sorted(d % v.ndim for d in dims)) == \
                        tuple(sorted(d % v.ndim for d in s_dims)):
                    leaf_out[label] = s_val
                else:
                    leaf_out[label] = snr_along_dims(v, dims, backend=backend,
                                                     mesh=mesh, spec=spec)
            out[name] = leaf_out
        else:
            out[name] = measure_leaf_snr(v, m, backend=backend, mesh=mesh, spec=spec)
    return out


@dataclasses.dataclass
class SNRTracker:
    """Accumulates time-averaged SNR (paper Eq. 4) plus full trajectories.

    The paper measures every 100 steps for the first 1000 steps, then every
    1000 steps; ``should_measure`` implements that cadence.
    """

    sums: Dict[str, Dict[str, float]] = dataclasses.field(default_factory=dict)
    count: int = 0
    trajectory: Dict[str, Dict[str, list]] = dataclasses.field(default_factory=dict)
    steps: list = dataclasses.field(default_factory=list)

    @staticmethod
    def should_measure(step: int, early_every: int = 100, late_every: int = 1000, early_until: int = 1000) -> bool:
        if step <= early_until:
            return step % early_every == 0
        return step % late_every == 0

    def update(self, snr_by_param: Mapping[str, Mapping[str, jnp.ndarray]], step: int) -> None:
        self.count += 1
        self.steps.append(int(step))
        for pname, by_k in snr_by_param.items():
            psum = self.sums.setdefault(pname, {})
            ptraj = self.trajectory.setdefault(pname, {})
            for k, v in by_k.items():
                val = float(v)
                psum[k] = psum.get(k, 0.0) + val
                ptraj.setdefault(k, []).append(val)

    def averaged(self) -> Dict[str, Dict[str, float]]:
        """E_t[SNR_K] per parameter per candidate K."""
        if self.count == 0:
            return {}
        return {p: {k: s / self.count for k, s in by_k.items()} for p, by_k in self.sums.items()}


def compression_ratio(meta: ParamMeta, shape: Sequence[int], k_axes: Optional[Tuple[str, ...]]) -> float:
    """Stored-elements fraction for a given compression choice (1.0 = Adam)."""
    if not k_axes:
        return 1.0
    dims = set(meta.dims_of(k_axes))
    kept = 1
    total = 1
    for i, s in enumerate(shape):
        total *= s
        if i not in dims:
            kept *= s
    return kept / total
