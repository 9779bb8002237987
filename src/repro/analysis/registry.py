"""Registry of kernel entry points for the static checks.

One declarative table of every Pallas kernel entry, the declared VMEM
buffer constant its dispatcher gates with, its output-signature variants
(``with_snr`` / ``with_health``), and a shape x dtype x orientation case
matrix. The kernel passes (:mod:`repro.analysis.kernelcheck`,
:mod:`repro.analysis.races`) iterate this table; consumers that need a
kernel's *signature* rather than its execution — the roofline gates in
``benchmarks/opt_speed.py`` — read it from here too
(:func:`snr_stat_lines`, :func:`health_stat_outputs`), so "what does this
kernel output" has exactly one definition.

Everything is ``ShapeDtypeStruct``-driven: building args, tracing, and
signatures never materialize an array.
"""
from __future__ import annotations

import math
from typing import Callable, Dict, List, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.kernels import fused_adam as _fa
from repro.kernels import megaplan as _mp
from repro.kernels import paged_attention as _pa
from repro.kernels import slim_update as _su
from repro.kernels import snr_stats as _ss
from repro.kernels.megaplan import (MEGA_ADAM_BUFS, MEGA_FINALIZE_BUFS,
                                    MEGA_PARTIAL_BUFS, MEGA_PRECOND_BUFS,
                                    MEGA_PRECOND_SNR_BUFS)
from repro.kernels.paged_attention import PAGED_ATTN_BUFS
from repro.kernels.slim_update import (FINALIZE_BUFS, PARTIAL_BUFS,
                                       PRECOND_BUFS, PRECOND_SNR_BUFS,
                                       UPDATE_BUFS)
from repro.kernels.snr_stats import CENTERED_BUFS, STATS_BUFS

from .jaxpr_tools import (entry_signature, find_pallas_eqns, pallas_info,
                          trace_entry)

f32 = jnp.float32
bf16 = jnp.bfloat16
i32 = jnp.int32


class Case(NamedTuple):
    """One abstract invocation shape for an entry."""

    label: str
    shape: Tuple[int, ...]          # (B, R, C) for strip entries, (R, C) for 2-D
    axis: Optional[int]             # strip reduction axis (None for 2-D tiles)
    dtypes: Tuple                   # dtype per positional arg
    kwargs: dict                    # static kwargs (block size etc.)
    kept: int                       # kept extent (for O(kept) classification)
    red: int                        # reduction extent (strip_fits input)


class Variant(NamedTuple):
    """One output-signature variant of an entry (appends extra outputs)."""

    name: str                       # "base" | "snr" | "health" | "snr+health"
    kwargs: dict
    bufs: Optional[int]             # declared strip n_bufs gate (None = 2-D tile)
    bufs_name: str


class KernelEntry(NamedTuple):
    name: str
    fn: Callable
    kind: str                       # "strip" | "tile2d" | "paged"
    arg_roles: Tuple[str, ...]      # "full" | "line" (strip), "full2d" (tile),
                                    # "q" | "pool" | "table" | "lengths" (paged)
    variants: Tuple[Variant, ...]   # variants[0] is the base signature
    cases: Tuple[Case, ...]
    # Concrete sample values for scalar-prefetch operands (page tables,
    # lengths) — index maps that read them can't be evaluated from grid
    # indices alone, so the race/aliasing analysis binds these samples.
    scalar_args: Optional[Callable[[Case], Tuple]] = None


def _dts(n: int, **over):
    """n float32 dtypes with per-slot overrides: _dts(3, s0=bf16)."""
    out = [f32] * n
    for key, dt in over.items():
        out[int(key[1:])] = dt
    return tuple(out)


def _strip_cases(n_args: int, *, bf16_slots: Tuple[int, ...],
                 fit_edge_bufs: Optional[int] = None) -> Tuple[Case, ...]:
    """The standard strip case matrix: minor/major orientation, a bf16
    storage case, a ragged (pad-and-recurse) kept extent, and optionally a
    reduction extent that lands exactly on the VMEM fit boundary for the
    entry's base buffer count."""
    over = {f"s{i}": bf16 for i in bf16_slots}
    cases = [
        Case("minor", (2, 8, 128), 1, _dts(n_args), {"block": 4}, kept=8, red=128),
        Case("major", (2, 128, 8), 0, _dts(n_args), {"block": 4}, kept=8, red=128),
        Case("minor-bf16", (2, 8, 128), 1, _dts(n_args, **over), {"block": 4},
             kept=8, red=128),
        Case("ragged", (1, 13, 128), 1, _dts(n_args), {"block": 4}, kept=13, red=128),
    ]
    if fit_edge_bufs is not None:
        from repro.kernels.tiling import VMEM_BUDGET
        red = VMEM_BUDGET // (4 * fit_edge_bufs)
        cases.append(Case("fit-edge", (1, 2, red), 1, _dts(n_args), {"block": 4},
                          kept=2, red=red))
    return tuple(cases)


def _finalize_with_ek(m_new, v_line, ek, **kw):
    return _su.slim_finalize_batched(m_new, v_line, ek=ek, **kw)


def _mega_finalize_with_ek(m_new, v_line, bc1, bc2, ek, **kw):
    return _mp.mega_slim_finalize_batched(m_new, v_line, bc1, bc2, ek=ek, **kw)


_TILE2D_CASES = (
    Case("aligned", (256, 512), None, _dts(4), {}, kept=256, red=512),
    Case("ragged-bf16", (300, 700), None, _dts(4, s0=bf16, s1=bf16), {},
         kept=300, red=700),
)

# Paged-attention case geometry rides in Case.kwargs (pool pages, page size,
# kv heads, table width) — static *shape* inputs, not kwargs of the entry;
# case_kwargs strips them before invocation.
_PAGED_GEOM = ("pages", "page", "kv", "max_pages")


def _paged_case(label: str, b: int, c: int, h: int, kv: int, hd: int,
                page: int, max_pages: int, *, qdt=f32, pooldt=f32) -> Case:
    # pool sized so the sample table below can hold b*max_pages distinct
    # non-null page ids — the page-table index-map check needs injective
    # samples to be meaningful
    pages = b * max_pages + 1
    return Case(label, (b, c, h, hd), None, (qdt, pooldt, i32, i32),
                {"pages": pages, "page": page, "kv": kv,
                 "max_pages": max_pages},
                kept=c * h, red=page * 2 * kv * hd)


def _paged_scalar_samples(case: Case):
    """(table, lengths) samples for the scalar-prefetch index maps: distinct
    non-null page ids per (row, slot) so aliasing/identity analysis sees a
    representative table, and ragged lengths including an inactive row."""
    import numpy as np

    b = case.shape[0]
    kw = case.kwargs
    mp, page = kw["max_pages"], kw["page"]
    table = (1 + np.arange(b * mp, dtype=np.int32)).reshape(b, mp)
    table %= np.int32(kw["pages"])
    lengths = np.asarray([(i * (mp * page)) // max(b, 1) for i in range(b)],
                         np.int32)
    return table, lengths


_PAGED_CASES = (
    _paged_case("decode", 3, 1, 4, 2, 8, 4, 4),
    _paged_case("decode-ragged", 2, 1, 4, 2, 8, 4, 5),
    _paged_case("decode-bf16", 3, 1, 4, 2, 8, 4, 4, qdt=bf16, pooldt=bf16),
    _paged_case("chunk", 1, 4, 4, 2, 8, 8, 4),
    _paged_case("chunk-bf16", 1, 4, 4, 2, 8, 8, 4, qdt=bf16, pooldt=bf16),
)

ENTRIES: Tuple[KernelEntry, ...] = (
    KernelEntry(
        "fused_adam", _fa.fused_adam, "tile2d", ("full2d",) * 4,
        (Variant("base", {"lr": 1e-3}, None, "-"),),
        _TILE2D_CASES,
    ),
    KernelEntry(
        "adam_precond", _fa.adam_precond, "tile2d", ("full2d",) * 3,
        (Variant("base", {}, None, "-"),
         Variant("health", {"with_health": True}, None, "-")),
        (Case("aligned", (256, 512), None, _dts(3), {}, kept=256, red=512),
         Case("ragged-bf16", (300, 700), None, _dts(3, s0=bf16), {},
              kept=300, red=700)),
    ),
    KernelEntry(
        "slim_update_batched", _su.slim_update_batched, "strip",
        ("full", "full", "full", "line"),
        (Variant("base", {"lr": 1e-3}, UPDATE_BUFS, "UPDATE_BUFS"),),
        _strip_cases(4, bf16_slots=(0, 1)),
    ),
    KernelEntry(
        "slim_precond_batched", _su.slim_precond_batched, "strip",
        ("full", "full", "line"),
        (Variant("base", {}, PRECOND_BUFS, "PRECOND_BUFS"),
         Variant("snr", {"with_snr": True}, PRECOND_SNR_BUFS, "PRECOND_SNR_BUFS"),
         Variant("health", {"with_health": True}, PRECOND_BUFS, "PRECOND_BUFS"),
         Variant("snr+health", {"with_snr": True, "with_health": True},
                 PRECOND_SNR_BUFS, "PRECOND_SNR_BUFS")),
        _strip_cases(3, bf16_slots=(0,), fit_edge_bufs=PRECOND_BUFS),
    ),
    KernelEntry(
        "slim_partial_stats_batched", _su.slim_partial_stats_batched, "strip",
        ("full", "full"),
        (Variant("base", {}, PARTIAL_BUFS, "PARTIAL_BUFS"),
         Variant("snr", {"with_snr": True}, PARTIAL_BUFS, "PARTIAL_BUFS"),
         Variant("health", {"with_health": True}, PARTIAL_BUFS, "PARTIAL_BUFS"),
         Variant("snr+health", {"with_snr": True, "with_health": True},
                 PARTIAL_BUFS, "PARTIAL_BUFS")),
        _strip_cases(2, bf16_slots=(0,)),
    ),
    KernelEntry(
        "slim_finalize_batched[ek]", _finalize_with_ek, "strip",
        ("full", "line", "line"),
        (Variant("base", {}, FINALIZE_BUFS, "FINALIZE_BUFS"),),
        _strip_cases(3, bf16_slots=()),
    ),
    KernelEntry(
        "slim_finalize_batched[owner]", _su.slim_finalize_batched, "strip",
        ("full", "line"),
        (Variant("base", {"ek": None}, FINALIZE_BUFS, "FINALIZE_BUFS"),),
        _strip_cases(2, bf16_slots=()),
    ),
    # Megaplan entries: inputs are always f32 (gather_group casts every
    # segment to the compute dtype before concatenation), so there are no
    # bf16 cases — the f32-compute contract is enforced structurally at the
    # gather, not inside the kernel body.
    KernelEntry(
        "mega_adam_update", _mp.mega_adam_update, "tile2d",
        ("full2d", "full2d", "full2d", "line2d", "line2d"),
        (Variant("base", {}, MEGA_ADAM_BUFS, "MEGA_ADAM_BUFS"),
         Variant("health", {"with_health": True}, MEGA_ADAM_BUFS,
                 "MEGA_ADAM_BUFS")),
        (Case("aligned", (256, 512), None, _dts(5), {}, kept=256, red=512),
         Case("ragged", (300, 512), None, _dts(5), {}, kept=300, red=512)),
    ),
    KernelEntry(
        "mega_slim_update_batched", _mp.mega_slim_update_batched, "strip",
        ("full", "full", "line", "line", "line"),
        (Variant("base", {}, MEGA_PRECOND_BUFS, "MEGA_PRECOND_BUFS"),
         Variant("snr", {"with_snr": True}, MEGA_PRECOND_SNR_BUFS,
                 "MEGA_PRECOND_SNR_BUFS"),
         Variant("health", {"with_health": True}, MEGA_PRECOND_BUFS,
                 "MEGA_PRECOND_BUFS"),
         Variant("snr+health", {"with_snr": True, "with_health": True},
                 MEGA_PRECOND_SNR_BUFS, "MEGA_PRECOND_SNR_BUFS")),
        _strip_cases(5, bf16_slots=(), fit_edge_bufs=MEGA_PRECOND_BUFS),
    ),
    KernelEntry(
        "mega_slim_partial_stats_batched", _mp.mega_slim_partial_stats_batched,
        "strip", ("full", "full"),
        (Variant("base", {}, MEGA_PARTIAL_BUFS, "MEGA_PARTIAL_BUFS"),
         Variant("snr", {"with_snr": True}, MEGA_PARTIAL_BUFS,
                 "MEGA_PARTIAL_BUFS"),
         Variant("health", {"with_health": True}, MEGA_PARTIAL_BUFS,
                 "MEGA_PARTIAL_BUFS"),
         Variant("snr+health", {"with_snr": True, "with_health": True},
                 MEGA_PARTIAL_BUFS, "MEGA_PARTIAL_BUFS")),
        _strip_cases(2, bf16_slots=()),
    ),
    KernelEntry(
        "mega_slim_finalize_batched[ek]", _mega_finalize_with_ek, "strip",
        ("full", "line", "line", "line", "line"),
        (Variant("base", {}, MEGA_FINALIZE_BUFS, "MEGA_FINALIZE_BUFS"),),
        _strip_cases(5, bf16_slots=()),
    ),
    KernelEntry(
        "mega_slim_finalize_batched[owner]", _mp.mega_slim_finalize_batched,
        "strip", ("full", "line", "line", "line"),
        (Variant("base", {"ek": None}, MEGA_FINALIZE_BUFS,
                 "MEGA_FINALIZE_BUFS"),),
        _strip_cases(4, bf16_slots=()),
    ),
    KernelEntry(
        "snr_stats_batched", _ss.snr_stats_batched, "strip", ("full",),
        (Variant("base", {}, STATS_BUFS, "STATS_BUFS"),),
        _strip_cases(1, bf16_slots=(0,)),
    ),
    KernelEntry(
        "snr_stats_centered_batched", _ss.snr_stats_centered_batched, "strip",
        ("full",),
        (Variant("base", {}, CENTERED_BUFS, "CENTERED_BUFS"),),
        _strip_cases(1, bf16_slots=(0,)),
    ),
    KernelEntry(
        "snr_stats_centered_partial_batched",
        _ss.snr_stats_centered_partial_batched, "strip", ("full",),
        (Variant("base", {}, CENTERED_BUFS, "CENTERED_BUFS"),),
        _strip_cases(1, bf16_slots=(0,)),
    ),
    KernelEntry(
        "paged_attention", _pa.paged_attention, "paged",
        ("q", "pool", "table", "lengths"),
        (Variant("base", {}, PAGED_ATTN_BUFS, "PAGED_ATTN_BUFS"),),
        _PAGED_CASES,
        scalar_args=_paged_scalar_samples,
    ),
)

ENTRY_MAP: Dict[str, KernelEntry] = {e.name: e for e in ENTRIES}


def case_args(entry: KernelEntry, case: Case) -> Tuple[jax.ShapeDtypeStruct, ...]:
    out = []
    for role, dt in zip(entry.arg_roles, case.dtypes):
        if role == "line":
            b, r, c = case.shape
            shape = (b, r, 1) if case.axis == 1 else (b, 1, c)
        elif role == "line2d":   # per-row operand of a 2-D tile entry
            shape = (case.shape[0], 1)
        elif role == "pool":
            kw = case.kwargs
            shape = _pa.pool_shape(kw["pages"], kw["page"], kw["kv"],
                                   case.shape[3])
        elif role == "table":
            shape = (case.shape[0], case.kwargs["max_pages"])
        elif role == "lengths":
            shape = (case.shape[0],)
        else:  # "full" (B, R, C), "full2d" (R, C), "q" (B, C, H, hd)
            shape = case.shape
        out.append(jax.ShapeDtypeStruct(shape, dt))
    return tuple(out)


def case_kwargs(entry: KernelEntry, case: Case, variant: Variant) -> dict:
    kw = dict(case.kwargs)
    kw.update(variant.kwargs)
    if entry.kind == "strip":
        kw["axis"] = case.axis
    elif entry.kind == "paged":
        for k in _PAGED_GEOM:
            kw.pop(k, None)
    return kw


def signature(entry: KernelEntry, case: Case, variant: Variant):
    """Flat output ShapeDtypeStructs of (entry, case, variant) — eval_shape."""
    return entry_signature(entry.fn, *case_args(entry, case),
                           **case_kwargs(entry, case, variant))


def signature_key(entry: KernelEntry, case: Case, variant: Variant) -> str:
    return f"{entry.name}::{case.label}::{variant.name}"


def encode_signature(sig) -> List[List[str]]:
    return [["x".join(str(d) for d in s.shape), jnp.dtype(s.dtype).name]
            for s in sig]


def all_signatures() -> Dict[str, List[List[str]]]:
    """Every registered (entry, case, variant) signature, golden-file form."""
    out = {}
    for entry in ENTRIES:
        for case in entry.cases:
            for variant in entry.variants:
                out[signature_key(entry, case, variant)] = encode_signature(
                    signature(entry, case, variant))
    return out


_TRACE_CACHE: Dict[str, list] = {}


def traced_infos(entry: KernelEntry, case: Case, variant: Variant) -> list:
    """PallasInfo list for (entry, case, variant), traced once per process —
    kernelcheck and the race detector share the same traces."""
    key = signature_key(entry, case, variant)
    if key not in _TRACE_CACHE:
        cj = trace_entry(entry.fn, *case_args(entry, case),
                         **case_kwargs(entry, case, variant))
        samples = entry.scalar_args(case) if entry.scalar_args else None
        _TRACE_CACHE[key] = [pallas_info(e, scalar_samples=samples)
                             for e in find_pallas_eqns(cj.jaxpr)]
    return _TRACE_CACHE[key]


def variant_extra_outputs(entry_name: str, case_label: str, variant_name: str):
    """The outputs a variant appends beyond the entry's base signature."""
    entry = ENTRY_MAP[entry_name]
    case = next(c for c in entry.cases if c.label == case_label)
    variant = next(v for v in entry.variants if v.name == variant_name)
    base = signature(entry, case, entry.variants[0])
    var = signature(entry, case, variant)
    return var[len(base):]


# ---------------------------------------------------------------------------
# Signature consumers (the opt_speed roofline gates read these)
# ---------------------------------------------------------------------------


def snr_stat_lines():
    """Per-regime extra-output counts of the ``with_snr`` kernel variants,
    read from the registry's eval_shape signatures, plus the shapes of any
    extra output that is *not* line-shaped — the fused-SNR claim is that a
    measure step adds O(kept) stat lines and zero full-size passes, so the
    gate observes the kernels' actual signatures rather than a constant that
    restates the model's own assumption.

    Returns ``({'psum': n, 'local': n, 'jnp': n}, full_size_outputs)``; a
    non-empty second element means a with_snr variant grew a full-size
    output. The jnp-fallback regime fuses the same centered sums into the
    XLA pass, so it is charged like the single-kernel (local) form.
    """
    case = "minor"
    full = math.prod(ENTRY_MAP["slim_partial_stats_batched"]
                     .cases[0].shape)
    partial = variant_extra_outputs("slim_partial_stats_batched", case, "snr")
    precond = variant_extra_outputs("slim_precond_batched", case, "snr")
    oversize = [tuple(o.shape) for o in list(partial) + list(precond)
                if math.prod(o.shape) >= full]
    return ({"psum": len(partial), "local": len(precond),
             "jnp": len(precond)}, oversize)


def health_stat_outputs():
    """Extra-output shapes of every kernel's ``with_health`` variant, read
    from the registry's signatures — the anomaly-guard claim is O(1) scalars
    per leaf riding the existing update pass, so each entry must append
    exactly one tiny accumulator.

    Returns a list of ``(kernel_name, extra_output_shapes)``.
    """
    out = []
    for name in ("adam_precond", "slim_precond_batched",
                 "slim_partial_stats_batched"):
        entry = ENTRY_MAP[name]
        case = entry.cases[0].label
        extras = variant_extra_outputs(name, case, "health")
        out.append((name, [tuple(o.shape) for o in extras]))
    return out
