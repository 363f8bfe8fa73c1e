"""The program analyzer: trace → jaxpr audit, lower → donation audit,
compile → memory/artifact audit. Nothing is ever executed.

Checks (rule ids continue foldlint's F-numbering; program-level checks are
F15x, cross-program/recompilation checks are F16x — see
tools/foldprog/RULES.md):

  F151  float64/complex leak — the jaxpr is re-traced under x64 semantics
        (`jax.enable_x64(True)`); any f64/c128 aval means the code
        relies on JAX's 32-bit canonicalization instead of dtype
        discipline, and would silently double its FLOPs/bytes under an
        x64-enabled host. (int64 from index-producing primitives like
        argsort is tolerated inside the program — it cannot exist at
        runtime under the production config.)
  F152  64-bit / weak-typed interface — program inputs and outputs must be
        32-bit-or-smaller and not weakly typed: a 64-bit or weak aval at
        the interface is storage blowup and shape-polymorphic promotion
        waiting to happen.
  F153  donation dropped — the lowered module must carry exactly the
        expected number of donated (aliased) parameters
        (`tf.aliasing_output` / `jax.buffer_donor` annotations): a
        refactor that loses `donate_argnums` doubles peak memory on
        accelerators, invisibly on CPU.
  F154  memory budget — memory_analysis() temp / peak bytes over the
        spec's ceiling.
  F155  host callback — pure_callback/io_callback/debug prints inside a
        hot-path program stall the async dispatch pipeline.
  F156  primitive budget — gather/scatter/while counts over the spec's
        ceiling (the HBM-round-trip shape of the beam loop).
  F161  recompilation budget — a bucketed family must lower exactly one
        distinct program per bucket shape, at most `max_programs`.
"""
from __future__ import annotations

import collections
import dataclasses
import time
from typing import Any, Iterable

import jax

from repro.analysis.programs import ProgramSpec

__all__ = ["Violation", "ProgramReport", "CompiledMeasure", "memory_dict",
           "lower_compile", "analyze_program", "analyze_family",
           "CHECK_DOCS"]

CHECK_DOCS = {
    "F151": "float64/complex aval under x64 tracing (dtype discipline leak)",
    "F152": "64-bit or weak-typed program input/output",
    "F153": "donated-parameter count differs from the spec's expectation",
    "F154": "memory_analysis temp/peak bytes over the program budget",
    "F155": "host callback primitive inside a hot-path program",
    "F156": "gather/scatter/while primitive count over the program budget",
    "F161": "bucketed family lowers more distinct programs than its budget",
}

# primitives whose presence in a lowered hot-path program means a host
# round-trip per execution
_CALLBACK_PRIMS = ("pure_callback", "io_callback", "callback",
                   "host_callback", "outside_call", "debug_callback",
                   "debug_print")

_BAD_X64 = ("float64", "complex128")
_BAD_IFACE = ("float64", "int64", "uint64", "complex128")


@dataclasses.dataclass(frozen=True)
class Violation:
    check: str
    program: str
    message: str

    def render(self) -> str:
        return f"{self.program}: {self.check} {self.message}"


@dataclasses.dataclass
class ProgramReport:
    name: str
    fingerprint: dict
    violations: list[Violation]


@dataclasses.dataclass
class CompiledMeasure:
    """One lower+compile pass over a jitted program (shared by the gate,
    launch/dryrun.py and benchmarks/roofline.py — the ONE lowering path)."""
    lowered: Any
    compiled: Any
    t_lower_s: float
    t_compile_s: float
    memory: dict

    def hlo_text(self) -> str:
        return self.compiled.as_text()

    def cost_analysis(self) -> dict:
        cost = self.compiled.cost_analysis()
        # older jax returns a one-element list
        if isinstance(cost, (list, tuple)):
            cost = cost[0] if cost else {}
        return dict(cost or {})


def memory_dict(compiled) -> dict:
    """memory_analysis() as a plain dict (fields are backend-optional)."""
    mem = compiled.memory_analysis()
    return {
        "argument_bytes": getattr(mem, "argument_size_in_bytes", None),
        "output_bytes": getattr(mem, "output_size_in_bytes", None),
        "temp_bytes": getattr(mem, "temp_size_in_bytes", None),
        "generated_code_bytes": getattr(mem, "generated_code_size_in_bytes",
                                        None),
    }


def lower_compile(jit_fn, *args, **kwargs) -> CompiledMeasure:
    """`.lower().compile()` with timings + memory_analysis.

    Compilation is where sharding mismatches, OOMs and unsupported
    collectives fail — which is the point of a dry run."""
    t0 = time.perf_counter()
    lowered = jit_fn.lower(*args, **kwargs)
    t_lower = time.perf_counter() - t0
    t0 = time.perf_counter()
    compiled = lowered.compile()
    t_compile = time.perf_counter() - t0
    return CompiledMeasure(lowered=lowered, compiled=compiled,
                           t_lower_s=t_lower, t_compile_s=t_compile,
                           memory=memory_dict(compiled))


# ------------------------------------------------------------ jaxpr walks
def _iter_eqns(jaxpr):
    """Yield every eqn in a jaxpr, recursing into sub-jaxprs (pjit bodies,
    while cond/body, scan/cond branches, vmap-of-closed-call, ...)."""
    from jax.extend import core as jcore
    stack = [jaxpr]
    while stack:
        j = stack.pop()
        for eqn in j.eqns:
            yield eqn
            for val in eqn.params.values():
                vals = (val if isinstance(val, (tuple, list)) else (val,))
                for x in vals:
                    if isinstance(x, jcore.ClosedJaxpr):
                        stack.append(x.jaxpr)
                    elif isinstance(x, jcore.Jaxpr):
                        stack.append(x)


def _aval_str(aval) -> str:
    shape = ",".join(str(d) for d in getattr(aval, "shape", ()))
    return f"{aval.dtype}[{shape}]"


def _is_abstract(x) -> bool:
    """Does this argument hold any array leaves (ShapeDtypeStruct)?"""
    return any(isinstance(leaf, jax.ShapeDtypeStruct)
               for leaf in jax.tree_util.tree_leaves(x))


def _trace(jit_fn, args, kwargs):
    """make_jaxpr over the spec's call, tracing ONLY the array arguments.

    Static configs (NamedTuples of python scalars) must be closed over,
    not traced: make_jaxpr would otherwise hand the jitted function
    tracers for its static_argnames, which are required to be hashable."""
    dyn_idx = [i for i, a in enumerate(args) if _is_abstract(a)]
    dyn_keys = [k for k, v in kwargs.items() if _is_abstract(v)]

    def call(*dyn):
        full = list(args)
        for i, v in zip(dyn_idx, dyn[:len(dyn_idx)]):
            full[i] = v
        kw = dict(kwargs)
        for k, v in zip(dyn_keys, dyn[len(dyn_idx):]):
            kw[k] = v
        return jit_fn(*full, **kw)

    dyn_args = [args[i] for i in dyn_idx] + [kwargs[k] for k in dyn_keys]
    return jax.make_jaxpr(call)(*dyn_args)


def primitive_counts(closed_jaxpr) -> dict[str, int]:
    counts: collections.Counter = collections.Counter()
    for eqn in _iter_eqns(closed_jaxpr.jaxpr):
        counts[eqn.primitive.name] += 1
    return dict(sorted(counts.items()))


def _count_donated(lowered) -> int:
    """Donated parameters as annotated in the lowered module.

    The LOWERED IR is audited (not the compiled executable's alias table)
    deliberately: CPU ignores donation at compile time, so the compiled
    table would be empty everywhere and the check would be vacuous. What
    the gate protects is the *declaration* surviving refactors — the
    accelerator honors it even when the CPU dry-run cannot."""
    txt = lowered.as_text()
    return txt.count("tf.aliasing_output") + txt.count("jax.buffer_donor")


# ---------------------------------------------------------------- checks
def _check_dtypes(spec: ProgramSpec, jit_fn, args, kwargs):
    """F151/F152: re-trace under x64 semantics and audit avals."""
    with jax.enable_x64(True):
        closed = _trace(jit_fn, args, kwargs)
    f64 = sorted({
        _aval_str(v.aval)
        for eqn in _iter_eqns(closed.jaxpr) for v in eqn.outvars
        if getattr(getattr(v, "aval", None), "dtype", None) is not None
        and str(v.aval.dtype) in _BAD_X64})
    iface = sorted(
        {f"in:{_aval_str(v.aval)}" for v in closed.jaxpr.invars
         if str(v.aval.dtype) in _BAD_IFACE}
        | {f"out:{_aval_str(v.aval)}" for v in closed.jaxpr.outvars
           if str(v.aval.dtype) in _BAD_IFACE})
    weak = sorted({_aval_str(v.aval) for v in closed.jaxpr.outvars
                   if getattr(v.aval, "weak_type", False)})
    out = []
    if f64:
        out.append(Violation("F151", spec.name,
                             f"float64 promotion under x64 tracing: "
                             f"{', '.join(f64[:6])}"))
    if iface:
        out.append(Violation("F152", spec.name,
                             f"64-bit interface avals: {', '.join(iface[:6])}"))
    if weak:
        out.append(Violation("F152", spec.name,
                             f"weak-typed outputs: {', '.join(weak[:6])}"))
    return out, {"f64": f64, "interface64": iface, "weak_outputs": weak}


def _check_budgets(spec: ProgramSpec, prims: dict, memory: dict):
    b = spec.budget
    out = []
    temp = memory.get("temp_bytes")
    if b.temp_bytes is not None and temp is not None and temp > b.temp_bytes:
        out.append(Violation("F154", spec.name,
                             f"temp bytes {temp:,} over budget "
                             f"{b.temp_bytes:,}"))
    peak = sum(memory.get(k) or 0 for k in
               ("argument_bytes", "output_bytes", "temp_bytes"))
    if b.peak_bytes is not None and peak > b.peak_bytes:
        out.append(Violation("F154", spec.name,
                             f"peak bytes {peak:,} over budget "
                             f"{b.peak_bytes:,}"))
    for attr, names in (("gather", ("gather",)),
                        ("scatter", ("scatter", "scatter-add", "scatter_add",
                                     "scatter_max", "scatter_min",
                                     "scatter_mul")),
                        ("while_loops", ("while",))):
        ceil = getattr(b, attr)
        if ceil is None:
            continue
        n = sum(v for k, v in prims.items() if k in names)
        if n > ceil:
            out.append(Violation("F156", spec.name,
                                 f"{attr} count {n} over budget {ceil}"))
    return out


def analyze_program(spec: ProgramSpec, *, run_compile: bool = True
                    ) -> ProgramReport:
    """Trace, lower and (optionally) compile one spec; return the
    fingerprint + budget violations. `run_compile=False` skips the compile
    (and therefore the memory audit) — used where only the trace-level
    checks matter and compile time is the bottleneck."""
    jit_fn, args, kwargs = spec.make()
    closed = _trace(jit_fn, args, kwargs)
    prims = primitive_counts(closed)
    in_avals = [_aval_str(v.aval) for v in closed.jaxpr.invars]
    out_avals = [_aval_str(v.aval) for v in closed.jaxpr.outvars]
    violations: list[Violation] = []

    n_cb = sum(v for k, v in prims.items()
               if any(k == c or k.startswith(c + "_") for c in _CALLBACK_PRIMS))
    if n_cb:
        violations.append(Violation(
            "F155", spec.name,
            f"{n_cb} host-callback primitive(s) in the lowered program"))

    dtype_viol, leaks = _check_dtypes(spec, jit_fn, args, kwargs)
    violations.extend(dtype_viol)

    memory: dict = {}
    donated = None
    if run_compile:
        measure = lower_compile(jit_fn, *args, **kwargs)
        donated = _count_donated(measure.lowered)
        memory = measure.memory
        if donated != spec.donate_expect:
            violations.append(Violation(
                "F153", spec.name,
                f"{donated} donated parameter(s) in the lowered module, "
                f"spec expects {spec.donate_expect} — "
                + ("donate_argnums dropped?" if donated < spec.donate_expect
                   else "update the spec's donate_expect")))
        violations.extend(_check_budgets(spec, prims, memory))

    fingerprint = {
        "program": spec.name,
        "family": spec.family,
        "in_avals": in_avals,
        "out_avals": out_avals,
        "primitives": prims,
        "donated": donated,
        "host_callbacks": n_cb,
        "x64_leaks": leaks,
        "memory": memory,
        "note": spec.budget.note,
    }
    return ProgramReport(name=spec.name, fingerprint=fingerprint,
                         violations=violations)


def analyze_family(family: str, specs: Iterable[ProgramSpec],
                   reports: dict[str, ProgramReport]) -> list[Violation]:
    """F161: the bucketed variants of `family` must lower exactly one
    distinct program per bucket, bounded by the family's max_programs."""
    specs = list(specs)
    sigs = {tuple(reports[s.name].fingerprint["in_avals"]) for s in specs}
    out = []
    if len(sigs) != len(specs):
        out.append(Violation(
            "F161", family,
            f"{len(specs)} bucket variants collapse to {len(sigs)} distinct "
            f"input signatures — redundant bucket in the menu"))
    ceil = max((s.budget.max_programs or 0) for s in specs) or None
    if ceil is not None and len(sigs) > ceil:
        out.append(Violation(
            "F161", family,
            f"{len(sigs)} distinct lowerings over the recompilation "
            f"budget {ceil}"))
    return out
