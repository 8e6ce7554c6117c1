"""Compiled-program registry: cost/HBM accounting for every jit site.

Host-side observability (steptime, MFU, goodput) says how long a step
took; this module says what XLA actually BUILT. Every jitted program
the framework dispatches — the train/eval/multi/pipelined steps, the
generate/beam programs, the serving engine's bucketed prefills, decode
step, and row insert — routes through :func:`instrument`, which on the
program's first (enabled) invocation lowers + compiles it through the
AOT API and records:

- ``cost_analysis()``: flops and bytes accessed per execution;
- ``memory_analysis()``: argument / output / temp / generated-code
  bytes, the donated (aliased) bytes the ``donate_argnums`` plumbing
  actually saved, and a peak-HBM estimate
  (``arg + out + temp + code - donated``, the residency XLA plans for);
- lowering and compile wall time.

Each registration appends to a process-level registry (:func:`programs`)
and emits a ``compile`` record through the active metrics registry, so
the run's JSONL carries the full program inventory next to its step
records (summarized by ``observe.report``'s "Programs" section and
:func:`budget_table`).

Graceful degradation is a contract, not an accident: backends or jax
versions that expose no analysis (or whose AOT path rejects the
arguments) still register the program — every analysis field is
explicitly ``None`` rather than absent, and the wrapped program always
executes through its ORIGINAL jitted callable, so telemetry can never
take down a run. The extra lower+compile for registration is absorbed
by the persistent compilation cache (utils/compilecache.py) that every
entrypoint enables.

Registration is gated (:func:`set_enabled`) because the AOT pass costs
a second trace: the Observatory turns it on for observed runs
(``--observe.programs``, default true — but only when a sink is
configured), serve/run.py likewise, and library use without either
stays zero-overhead (one bool check per call).
"""

from __future__ import annotations

import threading
import time
from typing import Any, Callable, Dict, List, Optional

from tensorflow_distributed_tpu.observe.registry import emit_event

_lock = threading.Lock()
_enabled = False
# Bumped on every set_enabled(True): lru-cached programs (generate's
# samplers, the engine's per-bucket prefills) survive across runs in
# one process, and each newly-enabled run deserves its own compile
# records in its own JSONL — a wrapper re-registers once per
# generation, not once per process.
_generation = 0
_programs: List[Dict[str, Any]] = []


def set_enabled(on: bool) -> None:
    """Arm (or disarm) registration. The Observatory calls this from
    ``--observe.programs``; tests and tools may call it directly."""
    global _enabled, _generation
    with _lock:
        if on and not _enabled:
            _generation += 1
        _enabled = bool(on)


def enabled() -> bool:
    return _enabled


def generation() -> int:
    return _generation


def programs() -> List[Dict[str, Any]]:
    """Snapshot of every compile record registered this process."""
    with _lock:
        return [dict(r) for r in _programs]


def reset() -> None:
    """Clear the process-level registry (test isolation)."""
    global _programs
    with _lock:
        _programs = []


def _first_mapping(value) -> Optional[Dict[str, Any]]:
    """cost_analysis() returns a dict on some jax versions and a
    one-per-device list of dicts on others — normalize to one dict."""
    if isinstance(value, (list, tuple)):
        value = value[0] if value else None
    if isinstance(value, dict):
        return value
    return None


def _round(v: Optional[float]) -> Optional[float]:
    return None if v is None else round(float(v), 6)


# Every analysis field a compile record (and a planner score) carries,
# in record order. extract_costs fills them all — explicitly None when
# the backend exposes nothing — so record SHAPE is stable everywhere.
COST_FIELDS = ("flops", "bytes_accessed", "argument_bytes",
               "output_bytes", "temp_bytes", "generated_code_bytes",
               "donated_bytes", "peak_hbm_bytes")


def extract_costs(compiled: Any) -> Dict[str, Any]:
    """``cost_analysis``/``memory_analysis`` of one AOT-compiled
    program, normalized to the :data:`COST_FIELDS` dict.

    THE one place the cross-jax-version key handling lives (dict vs
    per-device list-of-dicts cost_analysis, space-separated cost keys,
    memory_analysis attribute names) with the explicit-null
    degradation contract: a backend exposing no analysis yields a
    dict of ``None`` fields, never a missing key and never a raise.
    Shared by :func:`register_compiled` (the program registry) and
    the auto-layout planner's candidate scoring
    (analysis/planner/score.py)."""
    rec: Dict[str, Any] = {k: None for k in COST_FIELDS}
    if compiled is None:
        return rec
    try:
        cost = _first_mapping(compiled.cost_analysis())
    except Exception:
        cost = None
    if cost:
        if isinstance(cost.get("flops"), (int, float)):
            rec["flops"] = float(cost["flops"])
        if isinstance(cost.get("bytes accessed"), (int, float)):
            rec["bytes_accessed"] = float(cost["bytes accessed"])
    try:
        mem = compiled.memory_analysis()
    except Exception:
        mem = None
    if mem is not None:
        fields = {
            "argument_bytes": "argument_size_in_bytes",
            "output_bytes": "output_size_in_bytes",
            "temp_bytes": "temp_size_in_bytes",
            "generated_code_bytes": "generated_code_size_in_bytes",
            "donated_bytes": "alias_size_in_bytes",
        }
        for key, attr in fields.items():
            v = getattr(mem, attr, None)
            if isinstance(v, (int, float)):
                rec[key] = int(v)
        parts = (rec["argument_bytes"], rec["output_bytes"],
                 rec["temp_bytes"], rec["generated_code_bytes"])
        if all(p is not None for p in parts):
            # What XLA plans to hold resident while the program
            # runs; donated inputs alias their outputs, so they
            # are counted once, not twice.
            rec["peak_hbm_bytes"] = (
                sum(parts) - (rec["donated_bytes"] or 0))
    return rec


def aot_lower_compile(jitted: Callable, args: tuple = (),
                      kwargs: Optional[Dict[str, Any]] = None):
    """``jitted.lower(*args, **kwargs).compile()`` with wall clocks:
    returns ``(lowered, compiled, lower_s, compile_s)``. The ONE AOT
    capture path, shared by :func:`instrument`'s registration pass and
    the planner's candidate scoring — exceptions propagate; callers
    own their degradation policy (the registry degrades to a null
    record, the planner marks the candidate unscoreable)."""
    lower = getattr(jitted, "lower", None)
    if lower is None:
        raise TypeError("no .lower (not a jit callable)")
    t0 = time.perf_counter()
    lowered = lower(*args, **(kwargs or {}))
    t1 = time.perf_counter()
    compiled = lowered.compile()
    t2 = time.perf_counter()
    return lowered, compiled, t1 - t0, t2 - t1


def register_compiled(name: str, lowered: Any = None,
                      compiled: Any = None, *,
                      lower_s: Optional[float] = None,
                      compile_s: Optional[float] = None,
                      error: Optional[str] = None) -> Dict[str, Any]:
    """Record one compiled program's cost/memory accounting.

    ``lowered``/``compiled`` are the ``jax.stages`` objects from the
    AOT API (``jitted.lower(...)`` / ``.compile()``); either may be
    None — every analysis field degrades to an explicit ``None`` when
    the backend exposes nothing, so the record's SHAPE is stable across
    platforms and the report can always render the table.
    """
    rec: Dict[str, Any] = {
        "program": name,
        **extract_costs(compiled),
        "lower_s": _round(lower_s),
        "compile_s": _round(compile_s),
    }
    if error:
        rec["error"] = error[:300]
    with _lock:
        _programs.append(rec)
    emit_event("compile", **rec)
    return rec


class _InstrumentedProgram:
    """The :func:`instrument` wrapper: registers on the first enabled
    call, then (and on every later call) delegates to the ORIGINAL jit
    fast path — execution never routes through the slower AOT
    ``Compiled.__call__``, and a failed registration never fails the
    run. Unknown attributes forward to the wrapped PjitFunction
    (``.lower``/``.trace`` — the 1F1B parity tests drive
    the AOT API on the returned step), while callers may still SET
    their own attributes (pipeline_step's ``observe_hw_recompute``)."""

    def __init__(self, name: str, jitted: Callable):
        self._name = name
        self._jitted = jitted
        self._seen_generation = 0
        self.__wrapped__ = jitted
        self.__name__ = f"instrumented_{name}"

    def __call__(self, *args, **kwargs):
        if _enabled and self._seen_generation != _generation:
            self._seen_generation = _generation
            _register_from(self._name, self._jitted, args, kwargs)
        return self._jitted(*args, **kwargs)

    def __getattr__(self, attr):
        # Only reached for attributes NOT set on the wrapper itself.
        return getattr(self.__dict__["_jitted"], attr)


def instrument(name: str, jitted: Callable) -> Callable:
    """Wrap a jitted callable so its first enabled invocation registers
    the compiled program (see :class:`_InstrumentedProgram`)."""
    return _InstrumentedProgram(name, jitted)


def named_for_trace(name: str, fn: Callable) -> Callable:
    """Rename a PRE-jit function to its program name (sanitized —
    observe.xprof.sanitize is the one rule) so the XLA module lowers
    as ``jit_<program>`` and the profiler's ``hlo_module`` op tags
    attribute straight back to the registry name. Returns ``fn``."""
    from tensorflow_distributed_tpu.observe.xprof import sanitize

    fn.__name__ = sanitize(name)
    return fn


def instrument_jit(name: str, fn: Callable, **jit_kwargs) -> Callable:
    """``instrument(name, jax.jit(named_for_trace(name, fn), ...))`` —
    THE way a framework jit site registers: one name flows to the
    program registry, the compile record, the XLA module, and so the
    device-time attribution (observe/xprof.py)."""
    import jax

    return instrument(name, jax.jit(named_for_trace(name, fn),
                                    **jit_kwargs))


def _register_from(name: str, jitted: Callable, args, kwargs) -> None:
    """AOT lower+compile for the record; exceptions degrade to a
    null-field record (e.g. a non-jit callable, or an argument set the
    AOT path rejects) instead of propagating into the step."""
    try:
        lowered, compiled, lower_s, compile_s = aot_lower_compile(
            jitted, args, kwargs)
    except Exception as e:  # never take the run down for telemetry
        register_compiled(name, error=f"{type(e).__name__}: {e}")
        return
    register_compiled(name, lowered, compiled, lower_s=lower_s,
                      compile_s=compile_s)


def _latest_by_name() -> Dict[str, Dict[str, Any]]:
    out: Dict[str, Dict[str, Any]] = {}
    for rec in programs():
        out[rec["program"]] = rec
    return out


def hbm_budget() -> Optional[Dict[str, Any]]:
    """Process-level HBM budget rollup (latest record per program):
    how many programs are registered, the single largest resident
    program, and the sum over all of them (the worst case when
    executables stay loaded together, as the serving engine's do)."""
    latest = _latest_by_name()
    if not latest:
        return None
    peaks = [r["peak_hbm_bytes"] for r in latest.values()
             if r.get("peak_hbm_bytes") is not None]
    out: Dict[str, Any] = {"programs": len(latest)}
    if peaks:
        out["peak_hbm_bytes_max"] = max(peaks)
        out["peak_hbm_bytes_sum"] = sum(peaks)
    return out


def human_bytes(n: Optional[float]) -> str:
    """Byte counts for humans ("-" for null analyses) — the ONE
    formatter, shared with observe.report's Programs section."""
    if not isinstance(n, (int, float)):
        return "-"
    for unit in ("B", "KiB", "MiB", "GiB"):
        if abs(n) < 1024 or unit == "GiB":
            return f"{n:.1f}{unit}" if unit != "B" else f"{int(n)}B"
        n /= 1024
    return f"{n:.1f}GiB"


def budget_table() -> str:
    """Human-readable HBM budget table over the registered programs
    (latest record per name), largest resident program first."""
    latest = _latest_by_name()
    if not latest:
        return ""
    rows = sorted(latest.values(),
                  key=lambda r: -(r.get("peak_hbm_bytes") or 0))
    lines = [f"{'program':<28} {'flops':>12} {'peak_hbm':>10} "
             f"{'donated':>10} {'compile_s':>9}"]
    for r in rows:
        flops = ("-" if r.get("flops") is None
                 else f"{r['flops']:.3g}")
        comp = ("-" if r.get("compile_s") is None
                else f"{r['compile_s']:.3f}")
        lines.append(
            f"{r['program']:<28} {flops:>12} "
            f"{human_bytes(r.get('peak_hbm_bytes')):>10} "
            f"{human_bytes(r.get('donated_bytes')):>10} {comp:>9}")
    budget = hbm_budget() or {}
    if "peak_hbm_bytes_sum" in budget:
        lines.append(
            f"{'TOTAL (all resident)':<28} {'':>12} "
            f"{human_bytes(budget['peak_hbm_bytes_sum']):>10}")
    return "\n".join(lines)
