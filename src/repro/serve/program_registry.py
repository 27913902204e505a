"""Compiled-program registry: per-program cost cards and quality budgets.

XAMBA's methodology is bottleneck attribution — the paper found
CumSum/ReduceSum by *measuring per-op cost*, not by staring at wall
clocks — and the serve stack's own history repeats the lesson: the
XLA-CPU layout cliff (ROADMAP; 48 copies and a 1027-instruction block at
IDENTICAL compiled flops/bytes) was found by hand with ``make hlo-diff``
because nothing tracked compiled-program *quality* as a metric.

This module makes every program the engine warms up a first-class
observable.  Engines ``register()`` each jitted program (fused decode
step, per-bucket prefill, ``prefill_chunk``, ``verify_chunk``, the state
pools' row ops) with example argument *shapes*; the registry assigns a
stable **program id** (``p<N>:<name>``) that rides through recompile
sentinels and trace spans so ``launch/trace_report`` can attribute wall
time per program.  On demand — never on the serve hot path — it builds a
**program card** per program via jax's AOT API
(``fn.lower(*ShapeDtypeStructs).compile()``):

* ``cost_analysis``      — compiled flops / bytes accessed;
* ``memory_analysis``    — argument / output / temp-arena / codegen bytes;
* op fingerprint         — instruction count, opcode mix, **copy count**
  (``launch/hlo_analysis.op_fingerprint``);
* compile wall time      — the AOT compile of this exact program;
* roofline terms         — ``hlo_analysis.roofline_terms`` seconds.

Cards carry an optional :class:`ProgramBudget` — copy-count and
temp-arena ceilings — that fails loudly (``check_budgets``) when a
layout regression reappears: the budget trips, not a human with a diff.

Card building deliberately uses ``lower().compile()``, which does NOT
share the jit dispatch cache: a card costs one extra AOT compile.  That
is why cards are lazy (benchmarks, CLIs and tests build them; serving
never does) — registration itself only records shapes, so the hot path
and warmup stay untouched and the <= 2% tracing-overhead budget holds
trivially.
"""
from __future__ import annotations

import dataclasses
import time
from typing import Any, Callable, Dict, List, Optional, Sequence

MB = 2 ** 20

# Decode-cache layout pinned per family by benchmarks/bench_kpi_decode
# (BENCH_decode.json's ``decode_layout``): the layout each family's full
# -size decode program actually serves with, i.e. the one its budget
# must hold on.
PINNED_SCAN_LAYERS = {"mamba2-130m": True, "mamba-130m": False}

# Full-size budgets (docs/benchmarks.md, "layout cliff"): the good
# mamba2-130m decode layout compiles with 1 copy and a 37.7 MB temp
# arena; the per-layer cliff inserts 48 copies (and, scan-stacked on
# mamba1's side, a 191.8 MB temp blow-up).  Ceilings sit above the good
# layout with headroom and far below the cliff, keyed on the FULL
# d_model so reduced test configs never inherit them.
DEFAULT_BUDGETS = {
    ("mamba2-130m", "decode"): {"max_copies": 8,
                                "max_temp_bytes": 64 * MB,
                                "min_d_model": 768},
    ("mamba-130m", "decode"): {"max_copies": 64,
                               "max_temp_bytes": 64 * MB,
                               "min_d_model": 768},
}


def budget_for(cfg, program: str) -> Optional["ProgramBudget"]:
    """Default budget for ``(model config, program name)`` — None when the
    config is a reduced variant (budgets describe full-size programs)."""
    spec = DEFAULT_BUDGETS.get((getattr(cfg, "name", None), program))
    if spec is None:
        return None
    if getattr(cfg, "d_model", 0) < spec["min_d_model"]:
        return None
    return ProgramBudget(max_copies=spec["max_copies"],
                         max_temp_bytes=spec["max_temp_bytes"])


def shape_args(args: Sequence[Any]):
    """Example arguments -> ``jax.ShapeDtypeStruct`` pytrees (per leaf),
    so the registry never holds live buffers: donated arenas can be
    consumed freely after registration, and card builds lower from
    shapes alone."""
    import jax

    def leaf(x):
        if isinstance(x, jax.ShapeDtypeStruct):
            return x
        if hasattr(x, "shape") and hasattr(x, "dtype"):
            return jax.ShapeDtypeStruct(tuple(x.shape), x.dtype)
        return x          # static python leaf (rare) — lowered as-is

    return tuple(jax.tree.map(leaf, a) for a in args)


@dataclasses.dataclass(frozen=True)
class ProgramBudget:
    """Quality ceilings for one compiled program.  ``None`` disables a
    dimension.  Copy count is the layout-cliff tripwire (the cliff shows
    as copy/transpose insertion at equal flops); the temp-arena ceiling
    catches buffer-assignment blow-ups the op mix cannot see."""

    max_copies: Optional[int] = None
    max_temp_bytes: Optional[int] = None

    def to_dict(self) -> dict:
        return {"max_copies": self.max_copies,
                "max_temp_bytes": self.max_temp_bytes}


@dataclasses.dataclass
class ProgramCard:
    """One compiled program's cost/quality card (see module docstring)."""

    name: str
    program_id: str
    flops: float = 0.0
    bytes_accessed: float = 0.0
    argument_bytes: Optional[int] = None
    output_bytes: Optional[int] = None
    temp_bytes: Optional[int] = None
    generated_code_bytes: Optional[int] = None
    instructions: int = 0
    opcodes: int = 0
    copies: int = 0
    copy_bytes: int = 0
    # Mosaic (Pallas TPU) kernels in the compiled program: 0 in interpret
    # mode and wherever a kernel path ran the XLA chain instead.
    tpu_kernels: int = 0
    compile_s: float = 0.0
    roofline: Dict[str, Any] = dataclasses.field(default_factory=dict)
    budget: Optional[ProgramBudget] = None

    @property
    def roofline_s(self) -> float:
        """Modeled best-case seconds per call: the binding roofline term
        (compute vs memory; serve programs have no collectives)."""
        return max(self.roofline.get("compute_s", 0.0),
                   self.roofline.get("memory_s", 0.0))

    def check_budget(self) -> List[str]:
        """Budget violations (empty = within budget / no budget)."""
        out: List[str] = []
        b = self.budget
        if b is None:
            return out
        if b.max_copies is not None and self.copies > b.max_copies:
            out.append(
                f"program {self.name!r} ({self.program_id}): {self.copies} "
                f"copy ops exceed budget {b.max_copies} — layout "
                f"regression (see ROADMAP layout cliff / make hlo-diff)")
        if b.max_temp_bytes is not None and self.temp_bytes is not None \
                and self.temp_bytes > b.max_temp_bytes:
            out.append(
                f"program {self.name!r} ({self.program_id}): temp arena "
                f"{self.temp_bytes / MB:.1f} MB exceeds budget "
                f"{b.max_temp_bytes / MB:.1f} MB")
        return out

    def to_dict(self) -> dict:
        out = dataclasses.asdict(self)
        out.pop("budget", None)
        out["budget"] = self.budget.to_dict() if self.budget else None
        out["roofline_s"] = self.roofline_s
        out["budget_violations"] = self.check_budget()
        return out


def build_card(name: str, program_id: str, fn, example_args,
               budget: Optional[ProgramBudget] = None) -> ProgramCard:
    """AOT-compile ``fn`` at ``example_args`` shapes and measure the card.

    One fresh XLA compile per call (the AOT path shares no dispatch
    cache) — callers amortize via :meth:`ProgramRegistry.cards`."""
    from repro.launch.hlo_analysis import (buffer_assignment_stats,
                                           op_fingerprint, roofline_terms)

    t0 = time.perf_counter()
    compiled = fn.lower(*example_args).compile()
    compile_s = time.perf_counter() - t0

    ca = compiled.cost_analysis()
    if isinstance(ca, (list, tuple)):
        ca = ca[0] if ca else {}
    flops = float(ca.get("flops", 0.0) or 0.0)
    bytes_accessed = float(ca.get("bytes accessed", 0.0) or 0.0)

    mem = buffer_assignment_stats(compiled)
    text = compiled.as_text()
    fp = op_fingerprint(text)
    copies = fp.get("copy", {"count": 0, "bytes": 0})

    card = ProgramCard(
        name=name, program_id=program_id,
        flops=flops, bytes_accessed=bytes_accessed,
        argument_bytes=mem.get("argument_size_in_bytes"),
        output_bytes=mem.get("output_size_in_bytes"),
        temp_bytes=mem.get("temp_size_in_bytes"),
        generated_code_bytes=mem.get("generated_code_size_in_bytes"),
        instructions=sum(v["count"] for v in fp.values()),
        opcodes=len(fp),
        copies=copies["count"], copy_bytes=copies["bytes"],
        tpu_kernels=text.count('custom_call_target="tpu_custom_call"'),
        compile_s=round(compile_s, 4),
        roofline=roofline_terms(flops, bytes_accessed, 0.0, 0.0),
        budget=budget)
    return card


class ProgramRegistry:
    """Name -> (program id, lowering recipe, budget) for every compiled
    program one engine warms up.  Registration is cheap (shapes only);
    cards build lazily and cache until ``invalidate()`` (e.g. a backend
    -fallback rebuild swaps the jits underneath)."""

    def __init__(self):
        self._entries: Dict[str, dict] = {}
        self._order: List[str] = []

    # -- registration ------------------------------------------------------
    def register(self, name: str, fn=None, example_args=None, *,
                 fn_thunk: Optional[Callable[[], Any]] = None,
                 budget: Optional[ProgramBudget] = None) -> str:
        """Register (or refresh) a program.  ``fn`` is the jitted
        callable; ``fn_thunk`` defers resolution to card-build time (for
        lazily-built programs like the pools' row ops).  Re-registering a
        name keeps its id — a backend rebuild swaps the recipe, not the
        identity the trace spans reference."""
        if fn is None and fn_thunk is None:
            raise ValueError(f"program {name!r}: need fn or fn_thunk")
        if name in self._entries:
            entry = self._entries[name]
        else:
            entry = {"id": f"p{len(self._order)}:{name}"}
            self._entries[name] = entry
            self._order.append(name)
        entry["fn_thunk"] = fn_thunk if fn_thunk is not None \
            else (lambda f=fn: f)
        entry["example_args"] = (shape_args(example_args)
                                 if example_args is not None else None)
        if budget is not None or "budget" not in entry:
            entry["budget"] = budget
        entry.pop("card", None)      # recipe changed -> stale card
        return entry["id"]

    def set_example_args(self, name: str, example_args) -> None:
        entry = self._entries[name]
        entry["example_args"] = shape_args(example_args)
        entry.pop("card", None)

    # -- lookups -----------------------------------------------------------
    def names(self) -> List[str]:
        return list(self._order)

    def __contains__(self, name: str) -> bool:
        return name in self._entries

    def program_id(self, name: str) -> Optional[str]:
        entry = self._entries.get(name)
        return entry["id"] if entry else None

    def budget(self, name: str) -> Optional[ProgramBudget]:
        return self._entries[name].get("budget")

    # -- cards -------------------------------------------------------------
    def card(self, name: str, rebuild: bool = False) -> ProgramCard:
        """Build (or return the cached) card for one program.  Raises
        ``KeyError`` for unknown names and ``ValueError`` for programs
        registered without example args (no lowering recipe)."""
        entry = self._entries[name]
        if not rebuild and "card" in entry:
            return entry["card"]
        if entry.get("example_args") is None:
            raise ValueError(
                f"program {name!r} registered without example args — "
                f"no shapes to lower the card from")
        fn = entry["fn_thunk"]()
        if fn is None:
            raise ValueError(f"program {name!r}: recipe resolved to None "
                             f"(not built yet?)")
        entry["card"] = build_card(name, entry["id"], fn,
                                   entry["example_args"],
                                   budget=entry.get("budget"))
        return entry["card"]

    def cards(self, names: Optional[Sequence[str]] = None,
              rebuild: bool = False) -> Dict[str, ProgramCard]:
        """Cards for ``names`` (default: every program with example
        args).  Programs whose recipe cannot build (lazy op not built
        yet) are skipped when building the default set, and raise when
        requested by name."""
        if names is not None:
            return {n: self.card(n, rebuild=rebuild) for n in names}
        out = {}
        for n in self._order:
            if self._entries[n].get("example_args") is None:
                continue
            try:
                out[n] = self.card(n, rebuild=rebuild)
            except ValueError:
                continue
        return out

    def invalidate(self) -> None:
        """Drop cached cards (the jits were rebuilt, e.g. by a backend
        fallback); ids and budgets survive."""
        for entry in self._entries.values():
            entry.pop("card", None)

    # -- budgets -----------------------------------------------------------
    def check_budgets(self, names: Optional[Sequence[str]] = None
                      ) -> List[str]:
        """Build cards for every budgeted program and collect violations
        (empty list = all budgets hold)."""
        out: List[str] = []
        targets = names if names is not None else [
            n for n in self._order
            if self._entries[n].get("budget") is not None]
        for n in targets:
            out.extend(self.card(n).check_budget())
        return out

    def assert_budgets(self, names: Optional[Sequence[str]] = None) -> None:
        problems = self.check_budgets(names)
        if problems:
            raise RuntimeError("program budget violation(s):\n  " +
                               "\n  ".join(problems))

    def to_dict(self) -> Dict[str, dict]:
        """Every *built* card as plain dicts (for BENCH blocks / JSON
        dumps); call :meth:`cards` first to force building."""
        return {n: self._entries[n]["card"].to_dict()
                for n in self._order if "card" in self._entries[n]}
