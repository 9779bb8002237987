"""Device-free jaxpr introspection for the kernel passes.

Everything here operates on traces of kernel *entry points* over
``jax.ShapeDtypeStruct`` arguments — no kernel body ever executes and no
array is materialized. A traced entry contains one (or more) ``pallas_call``
equations; :func:`find_pallas_eqns` digs them out of any wrapping structure
(the pad-and-recurse entries trace straight through: padding happens in
Python, so the trace holds a single aligned call), and :func:`pallas_info`
normalizes each into a :class:`PallasInfo` the checks can interrogate:

  * block geometry per operand/output (shape, backing array, index_map as a
    callable evaluated through ``jaxpr_as_fun`` — still device-free);
  * grid + per-dim semantics (``pltpu.CompilerParams.dimension_semantics``;
    absent means every dim is sequential/"arbitrary");
  * the kernel body jaxpr, with ref reads (``get``) and writes (``swap``)
    collected per *root* ref through nested sub-jaxprs (``pl.when`` lowers
    to ``cond``), so RMW and compute-dtype contracts see conditional
    accesses too.
"""
from __future__ import annotations

import functools
import itertools
import math
from dataclasses import dataclass
from typing import Any, Callable, Dict, List, Optional, Sequence, Set, Tuple

import jax
import jax.numpy as jnp

from jax._src.pallas.core import Squeezed
from jax._src.state.types import AbstractRef
from jax.experimental.pallas import tpu as pltpu
from jax.extend.core import ClosedJaxpr, Jaxpr, Literal, Var, jaxpr_as_fun


def trace_entry(fn: Callable, *args, **kwargs) -> ClosedJaxpr:
    """``make_jaxpr`` of ``fn(*args, **kwargs)`` — args may (and should) be
    ``ShapeDtypeStruct``s; keyword arguments are bound statically."""
    return jax.make_jaxpr(functools.partial(fn, **kwargs))(*args)


def count_pallas_launches(fn: Callable, *args, **kwargs) -> int:
    """Number of ``pallas_call`` equations in one trace of ``fn`` — the
    launch count a jitted call pays per step. Device-free (make_jaxpr over
    whatever abstract/concrete args are given); the megakernel benches gate
    on this so the O(leaves) -> O(groups) claim doesn't ride on interp-mode
    wall clocks."""
    return len(find_pallas_eqns(trace_entry(fn, *args, **kwargs).jaxpr))


def entry_signature(fn: Callable, *args, **kwargs) -> List[Any]:
    """Flat list of output ``ShapeDtypeStruct``s of an entry (eval_shape)."""
    out = jax.eval_shape(functools.partial(fn, **kwargs), *args)
    return list(jax.tree_util.tree_leaves(out))


def _iter_sub_jaxprs(params: Dict[str, Any]):
    for v in params.values():
        if isinstance(v, ClosedJaxpr):
            yield v.jaxpr
        elif isinstance(v, Jaxpr):
            yield v
        elif isinstance(v, (tuple, list)):
            for x in v:
                if isinstance(x, ClosedJaxpr):
                    yield x.jaxpr
                elif isinstance(x, Jaxpr):
                    yield x


def find_pallas_eqns(jaxpr: Jaxpr) -> List[Any]:
    """All ``pallas_call`` equations in ``jaxpr``, recursing through control
    flow / call primitives (kernel bodies cannot nest pallas calls, so their
    params are not walked)."""
    out = []
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "pallas_call":
            out.append(eqn)
            continue
        for sub in _iter_sub_jaxprs(eqn.params):
            out.extend(find_pallas_eqns(sub))
    return out


@dataclass
class BlockInfo:
    """One operand/output block of a pallas_call."""

    role: str                      # "in" | "out"
    slot: int                      # index within the role
    block_shape: Tuple[int, ...]   # block dims (mapped/None dims -> 1)
    array_shape: Tuple[int, ...]
    array_dtype: Any
    index_map: Callable[..., Tuple[int, ...]]

    @property
    def elems(self) -> int:
        return math.prod(self.block_shape) if self.block_shape else 1

    def bytes_at(self, itemsize: int) -> int:
        return self.elems * itemsize


@dataclass
class PallasInfo:
    """Normalized view of one pallas_call equation."""

    grid: Tuple[int, ...]
    dimension_semantics: Tuple[str, ...]   # per grid dim; "arbitrary" default
    blocks_in: List[BlockInfo]
    blocks_out: List[BlockInfo]
    body: Jaxpr                            # kernel body jaxpr
    num_index_operands: int

    @property
    def blocks(self) -> List[BlockInfo]:
        return self.blocks_in + self.blocks_out

    def body_ref(self, block: BlockInfo) -> Var:
        """The body jaxpr invar (MemRef) backing ``block`` — body invars are
        ordered [index operands, inputs, outputs, scratch]."""
        off = self.num_index_operands
        if block.role == "out":
            off += len(self.blocks_in)
        return self.body.invars[off + block.slot]

    def footprint_bytes(self, itemsize: int = 4) -> int:
        """Per-instance VMEM block footprint. Charged at ``itemsize`` (f32 by
        default) for every block — the kernels cast all operands to f32 for
        compute, so 4 B/elem is the live cost regardless of storage dtype."""
        return sum(b.bytes_at(itemsize) for b in self.blocks)

    def full_block_count(self) -> int:
        """Number of full-size (largest) blocks per instance — the quantity
        the declared ``*_BUFS`` constants budget for (lines, stats and
        scalar operands are O(kept)/O(1) and don't count)."""
        top = max(b.elems for b in self.blocks)
        return sum(1 for b in self.blocks if b.elems == top)


def _norm_block_shape(shape) -> Tuple[int, ...]:
    """Block dims as ints: a squeezed dim (``None`` in the BlockSpec) is 1,
    every other dim (``pl.Blocked`` and kin) its ``block_size``."""
    return tuple(1 if isinstance(d, Squeezed) else int(d.block_size)
                 for d in shape)


def _index_map_fn(index_map_jaxpr: ClosedJaxpr,
                  scalar_samples: Optional[Sequence[Any]] = None
                  ) -> Callable[..., Tuple[int, ...]]:
    """Evaluate an index-map jaxpr at concrete grid indices. Scalar-prefetch
    kernels (``PrefetchScalarGridSpec``) hand every index map the prefetched
    operands (page tables, lengths) as extra invars after the grid indices;
    ``scalar_samples`` supplies concrete sample values for them so the maps
    stay evaluable device-free. Samples default to zeros of the invar avals
    — registry entries that alias through a lookup table provide real
    samples (see ``KernelEntry.scalar_args``) so collision analysis sees
    representative table contents."""
    invars = index_map_jaxpr.jaxpr.invars
    n_out = len(index_map_jaxpr.jaxpr.outvars)
    if any(isinstance(v.aval, AbstractRef) for v in invars):
        # Scalar-prefetch operands arrive as (S)MEM refs whose reads are
        # stateful `get`s; discharge turns them into plain array inputs
        # (appending the final ref values to the outputs, truncated below).
        from jax._src.state.discharge import discharge_state
        dj, dconsts = discharge_state(index_map_jaxpr.jaxpr,
                                      index_map_jaxpr.consts)
        f = jaxpr_as_fun(ClosedJaxpr(dj, dconsts))
    else:
        f = jaxpr_as_fun(index_map_jaxpr)
    extras = tuple(jnp.asarray(s) for s in (scalar_samples or ()))

    def call(*idx: int) -> Tuple[int, ...]:
        args = [jnp.int32(i) for i in idx]
        # invars = [grid indices..., scalar operands...]; fill any operand
        # slot not covered by a provided sample with aval-shaped zeros
        for v in invars[len(args):len(invars) - len(extras)]:
            args.append(jnp.zeros(v.aval.shape, v.aval.dtype))
        return tuple(int(x) for x in f(*args, *extras)[:n_out])

    return call


def pallas_info(eqn, scalar_samples: Optional[Sequence[Any]] = None) -> PallasInfo:
    gm = eqn.params["grid_mapping"]
    body = eqn.params["jaxpr"]
    grid = tuple(int(g) for g in gm.grid)
    n_idx = int(getattr(gm, "num_index_operands", 0))
    n_out = len(eqn.outvars)
    n_in = len(eqn.invars) - n_idx

    sem = None
    for cp in (eqn.params.get("compiler_params") or {}).values():
        if isinstance(cp, pltpu.CompilerParams):
            sem = cp.dimension_semantics
    if sem is None:
        sem = ("arbitrary",) * len(grid)
    sem = tuple(str(s) for s in sem)

    mappings = list(gm.block_mappings)
    assert len(mappings) == n_in + n_out, (
        f"block_mappings ({len(mappings)}) != inputs ({n_in}) + outputs ({n_out})")

    def mk(role: str, slot: int, bm) -> BlockInfo:
        sds = bm.array_aval
        return BlockInfo(
            role=role, slot=slot,
            block_shape=_norm_block_shape(bm.block_shape),
            array_shape=tuple(sds.shape), array_dtype=sds.dtype,
            index_map=_index_map_fn(bm.index_map_jaxpr, scalar_samples),
        )

    blocks_in = [mk("in", i, mappings[i]) for i in range(n_in)]
    blocks_out = [mk("out", i, mappings[n_in + i]) for i in range(n_out)]
    return PallasInfo(grid=grid, dimension_semantics=sem,
                      blocks_in=blocks_in, blocks_out=blocks_out,
                      body=body, num_index_operands=n_idx)


# ---------------------------------------------------------------------------
# Ref access collection (get/swap through nested sub-jaxprs)
# ---------------------------------------------------------------------------


@dataclass
class RefOp:
    """One ``get`` or ``swap`` on a root ref, wherever it occurs."""

    kind: str    # "get" | "swap"
    root: Var    # the body invar the accessed ref aliases
    eqn: Any
    jaxpr: Jaxpr  # the (sub-)jaxpr the access lives in


def _sub_jaxpr_bindings(eqn):
    """(sub_jaxpr, [(inner_var, outer_var), ...]) pairs for primitives whose
    sub-jaxprs rebind the outer operands — enough for the structures kernel
    bodies contain (``cond`` from ``pl.when``; generic 1:1 call wrappers)."""
    name = eqn.primitive.name
    if name == "cond":
        ops = eqn.invars[1:]  # invars[0] is the branch index
        for closed in eqn.params.get("branches", ()):
            sub = closed.jaxpr if isinstance(closed, ClosedJaxpr) else closed
            yield sub, list(zip(sub.invars, ops))
        return
    for sub in _iter_sub_jaxprs(eqn.params):
        if len(sub.invars) == len(eqn.invars):
            yield sub, list(zip(sub.invars, eqn.invars))


def collect_ref_ops(jaxpr: Jaxpr, env: Dict[Var, Var]) -> List[RefOp]:
    """All get/swap accesses in ``jaxpr`` (recursively) whose ref resolves —
    through ``env`` — to one of the root vars env maps to."""
    ops: List[RefOp] = []
    for eqn in jaxpr.eqns:
        name = eqn.primitive.name
        if name in ("get", "swap"):
            ref = eqn.invars[0]
            if isinstance(ref, Var) and ref in env:
                ops.append(RefOp(name, env[ref], eqn, jaxpr))
        for sub, binds in _sub_jaxpr_bindings(eqn):
            sub_env = {inner: env[outer]
                       for inner, outer in binds
                       if isinstance(outer, Var) and outer in env}
            if sub_env:
                ops.extend(collect_ref_ops(sub, sub_env))
    return ops


def ref_ops_for(info: PallasInfo) -> List[RefOp]:
    env = {v: v for v in info.body.invars if isinstance(v, Var)}
    return collect_ref_ops(info.body, env)


def var_consumers(jaxpr: Jaxpr, var: Var) -> List[Any]:
    """Equations in ``jaxpr`` (same level) that read ``var``."""
    return [e for e in jaxpr.eqns
            if any(isinstance(v, Var) and v is var for v in e.invars)]


def var_producer(jaxpr: Jaxpr, var: Var) -> Optional[Any]:
    """The equation in ``jaxpr`` (same level) that defines ``var``, if any."""
    for e in jaxpr.eqns:
        if any(v is var for v in e.outvars):
            return e
    return None


# ---------------------------------------------------------------------------
# Grid aliasing (non-injective index maps)
# ---------------------------------------------------------------------------


def _grid_points(grid: Sequence[int], per_dim: int = 4):
    """Representative grid points: every point for small grids; for large
    dims the first/last ``per_dim`` indices (constant and striding maps both
    collide within that sample)."""
    axes = []
    for n in grid:
        if n <= 2 * per_dim:
            axes.append(range(n))
        else:
            axes.append(sorted(set(range(per_dim)) | set(range(n - per_dim, n))))
    return itertools.product(*axes)


def aliased_grid_dims(block: BlockInfo, grid: Sequence[int]) -> Set[int]:
    """Grid dims along which ``block``'s index_map collides: dims in which two
    sampled grid points that map to the same block index differ. Empty set =
    injective over the sample (one block instance per grid point)."""
    seen: Dict[Tuple[int, ...], List[Tuple[int, ...]]] = {}
    for pt in _grid_points(grid):
        seen.setdefault(block.index_map(*pt), []).append(pt)
    dims: Set[int] = set()
    for pts in seen.values():
        if len(pts) < 2:
            continue
        base = pts[0]
        for other in pts[1:]:
            dims.update(d for d in range(len(grid)) if base[d] != other[d])
    return dims
