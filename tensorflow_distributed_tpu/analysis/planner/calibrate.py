"""Cost-model calibration: fit effective device rates to MEASURED steps.

The planner's roofline (score.py) divides AOT cost analysis by fixed
per-chip peaks — the TPU_HW table for known kinds, GENERIC_HW's
arbitrary-but-fixed ratios everywhere else. Fine for RANKING, useless
as wall-clock truth (on a CPU host a tiny step predicted at 0.26 ms
measured 18.6 ms). This module closes the
predicted→measured gap the TF paper's runtime closes internally
(PAPERS.md 1605.08695) and pjit-era systems close with profiler-driven
tuning (2204.06514): fit EFFECTIVE flops/s, HBM bytes/s, and
collective bytes/s from measured ``(program costs, step time)`` pairs
by least squares over the roofline's own terms, write an atomic
``calibration.json`` (platform/device-kind tagged, git-sha stamped),
and let ``score.detect_hardware(calibration=...)`` prefer the profile
over the static tables.

The model is the roofline plus a per-dispatch overhead intercept::

    ms = overhead + max(1e3*flops/F, 1e3*bytes/B) + 1e3*coll_bytes/C

The intercept is what the static tables structurally CANNOT express:
every real dispatch pays a fixed launch/host cost (large on CPU, small
but nonzero on TPU), and without it no single rate fits a batch-16 and
a batch-64 step at once. It never changes candidate RANKING at fixed
scale — every candidate pays it — but it is the difference between a
ranking device and a wall-clock predictor. The model is nonlinear in
(F, B, C) through the max, so the fit alternates: assign each sample
to its binding term under the current rates, then (overhead, 1/F, 1/B)
solve jointly as a LINEAR least squares over the assigned design
matrix (3x3 normal equations, pure python), and C updates on the
residual the max-term leaves. Parameters a sample set cannot constrain
(no collective traffic -> C; every sample compute-bound -> B) keep
their previous value — an unconstrained parameter must not wander; a
negative intercept clamps to zero and the rates re-solve without it.

Sample sources:

- ``samples_from_planbench(path)``: ``planbench_candidate`` lines
  (per-device ``flops`` / ``bytes_accessed`` / ``collective_bytes``
  beside ``measured_step_ms_min``) — many programs, one measurement
  each. The sweep that wrote this format went in PR 30; the reader
  goes with the planner's trial (ROADMAP.md C7);
- ``samples_from_metrics(path)``: a run's own metrics JSONL — join
  ``compile`` records (costs) with ``device_time`` records (measured
  ``device_ms_per_call`` from the xprof attribution) by program name.

Pure stdlib on purpose (module import is jax-free); the CLI::

    python -m tensorflow_distributed_tpu.analysis.planner.calibrate \
        --from-jsonl metrics.jsonl --out calibration.json
"""

from __future__ import annotations

import argparse
import hashlib
import json
import os
import sys
from typing import Any, Dict, List, Optional, Sequence

from tensorflow_distributed_tpu.utils.atomicio import atomic_write_json

CALIBRATION_VERSION = 1

#: the sample fields a fit consumes (measured_ms > 0 required;
#: flops/bytes numeric required; collective_bytes optional/0).
SAMPLE_FIELDS = ("flops", "bytes_accessed", "collective_bytes",
                 "measured_ms")


def _valid(samples: Sequence[Dict[str, Any]]) -> List[Dict[str, Any]]:
    out = []
    for s in samples:
        f, b = s.get("flops"), s.get("bytes_accessed")
        m = s.get("measured_ms")
        if (isinstance(f, (int, float)) and isinstance(b, (int, float))
                and isinstance(m, (int, float)) and m > 0
                and (f > 0 or b > 0)):
            out.append({"flops": float(f), "bytes_accessed": float(b),
                        "collective_bytes": float(
                            s.get("collective_bytes") or 0.0),
                        "measured_ms": float(m),
                        "key": s.get("key") or s.get("program")})
    return out


def _ls_rate(units: List[float], ms: List[float]) -> Optional[float]:
    """The closed-form least squares for one roofline term: minimize
    sum((1e3 * u_i / R - y_i)^2) over R > 0. Returns units/second
    (None when the samples can't constrain it)."""
    num = sum(u * y for u, y in zip(units, ms))
    den = sum(u * u for u in units)
    if num <= 0 or den <= 0:
        return None
    inv = num / (1e3 * den)   # seconds-per-unit * 1e... (ms = 1e3*u/R)
    return 1.0 / inv if inv > 0 else None


def _predict_ms(s: Dict[str, Any], F: float, B: float,
                C: Optional[float], overhead: float = 0.0) -> float:
    compute = 1e3 * s["flops"] / F
    memory = 1e3 * s["bytes_accessed"] / B
    coll = (1e3 * s["collective_bytes"] / C
            if C and s["collective_bytes"] else 0.0)
    return overhead + max(compute, memory) + coll


def _solve_normal(rows: List[List[float]], ys: List[float]
                  ) -> Optional[List[float]]:
    """min ||A x - y||_2 by the normal equations (tiny n — 3 params),
    Gaussian elimination with partial pivoting. None when singular."""
    n = len(rows[0])
    a = [[sum(r[i] * r[j] for r in rows) for j in range(n)]
         + [sum(r[i] * y for r, y in zip(rows, ys))]
         for i in range(n)]
    for col in range(n):
        piv = max(range(col, n), key=lambda r: abs(a[r][col]))
        if abs(a[piv][col]) < 1e-30:
            return None
        a[col], a[piv] = a[piv], a[col]
        for r in range(n):
            if r != col:
                k = a[r][col] / a[col][col]
                a[r] = [v - k * w for v, w in zip(a[r], a[col])]
    return [a[i][n] / a[i][i] for i in range(n)]


def fit_rates(samples: Sequence[Dict[str, Any]], iters: int = 20
              ) -> Dict[str, Any]:
    """Alternating least squares under the overhead + max-roofline
    model (module docstring).

    Returns ``{"peak_flops", "hbm_bw", "ici_bw", "overhead_ms",
    "samples", "mean_abs_rel_err", "median_abs_rel_err"}`` — rates are
    effective units/second; ici_bw is None when no sample moved
    collective bytes. Raises ValueError on an empty/unusable sample
    set."""
    ss = _valid(samples)
    if not ss:
        raise ValueError("no usable calibration samples (need numeric "
                         "flops/bytes_accessed and measured_ms > 0)")
    ms = [s["measured_ms"] for s in ss]
    # Init: each rate fit as if ITS term alone explained every sample.
    F = _ls_rate([s["flops"] for s in ss], ms) or 1e9
    B = _ls_rate([s["bytes_accessed"] for s in ss], ms) or 1e9
    O = 0.0
    with_coll = [s for s in ss if s["collective_bytes"] > 0]
    C = (_ls_rate([s["collective_bytes"] for s in with_coll],
                  [s["measured_ms"] for s in with_coll])
         if with_coll else None)
    for _ in range(iters):
        coll_ms = [(1e3 * s["collective_bytes"] / C
                    if C and s["collective_bytes"] else 0.0)
                   for s in ss]
        resid = [max(m - c, 1e-9) for m, c in zip(ms, coll_ms)]
        compute_bound = [1e3 * s["flops"] / F
                         >= 1e3 * s["bytes_accessed"] / B for s in ss]
        # Joint LINEAR solve for (overhead, 1/F, 1/B) under the
        # current assignment. Columns only for constrained params: an
        # empty group would make its column all-zero (singular).
        cols = ["o"] + (["F"] if any(compute_bound) else []) \
            + (["B"] if not all(compute_bound) else [])
        rows = []
        for s, cb in zip(ss, compute_bound):
            row = []
            for c in cols:
                if c == "o":
                    row.append(1.0)
                elif c == "F":
                    row.append(1e3 * s["flops"] if cb else 0.0)
                else:
                    row.append(0.0 if cb
                               else 1e3 * s["bytes_accessed"])
            rows.append(row)
        sol = _solve_normal(rows, resid)
        if sol is not None and sol[0] < 0:
            # Negative intercept is nonphysical: clamp to zero and
            # re-solve the rates without it.
            sol2 = _solve_normal([r[1:] for r in rows], resid)
            sol = None if sol2 is None else [0.0] + sol2
        if sol is not None:
            vals = dict(zip(cols, sol))
            O = max(vals.get("o", 0.0), 0.0)
            if vals.get("F", 0.0) > 0:
                F = 1.0 / vals["F"]
            if vals.get("B", 0.0) > 0:
                B = 1.0 / vals["B"]
        if with_coll:
            # Collective rate on what overhead + max-term leave.
            rc = [max(s["measured_ms"] - O
                      - max(1e3 * s["flops"] / F,
                            1e3 * s["bytes_accessed"] / B), 1e-9)
                  for s in with_coll]
            C = _ls_rate([s["collective_bytes"] for s in with_coll],
                         rc) or C
    errs = sorted(abs(_predict_ms(s, F, B, C, O) - s["measured_ms"])
                  / s["measured_ms"] for s in ss)
    return {
        "peak_flops": F, "hbm_bw": B, "ici_bw": C,
        "overhead_ms": round(O, 6),
        "samples": len(ss),
        "mean_abs_rel_err": round(sum(errs) / len(errs), 4),
        "median_abs_rel_err": round(errs[len(errs) // 2], 4),
    }


def rel_errors(samples: Sequence[Dict[str, Any]], peak_flops: float,
               hbm_bw: float, ici_bw: Optional[float],
               overhead_ms: float = 0.0) -> List[float]:
    """Per-sample |predicted - measured| / measured under given rates
    (compare calibrated against uncalibrated rates)."""
    return [abs(_predict_ms(s, peak_flops, hbm_bw, ici_bw, overhead_ms)
                - s["measured_ms"]) / s["measured_ms"]
            for s in _valid(samples)]


# --- profile IO --------------------------------------------------------

def make_profile(fit: Dict[str, Any], platform: str, device_kind: str,
                 source: str = "", devices: int = 0) -> Dict[str, Any]:
    """The calibration.json payload: effective rates + provenance.
    ``calibration_id`` is a short stable hash of platform/kind/rates —
    the id a ``plan`` record and a flight-recorder bundle carry, so a
    reader can name exactly which profile predicted what."""
    from tensorflow_distributed_tpu.observe.registry import git_sha

    eff = {"peak_flops": fit["peak_flops"], "hbm_bw": fit["hbm_bw"],
           "ici_bw": fit["ici_bw"],
           "overhead_ms": fit.get("overhead_ms", 0.0)}
    blob = json.dumps([platform, device_kind, eff], sort_keys=True)
    cal_id = (f"{platform}-"
              f"{hashlib.sha256(blob.encode()).hexdigest()[:10]}")
    return {
        "version": CALIBRATION_VERSION,
        "calibration_id": cal_id,
        "platform": platform,
        "device_kind": device_kind,
        "git_sha": git_sha(),
        "source": source,
        "devices": devices,
        "effective": eff,
        "fit": {k: fit[k] for k in ("samples", "mean_abs_rel_err",
                                    "median_abs_rel_err")},
    }


def write_calibration(profile: Dict[str, Any], path: str) -> None:
    """Atomic (tmp+fsync+rename) so a poller — or a crashed fit —
    never reads a torn profile."""
    atomic_write_json(path, profile, indent=2, trailing_newline=True)


def load_calibration(path: str) -> Dict[str, Any]:
    """Read + shape-check a profile; raises ValueError on junk (a
    mis-pointed --plan-calibration must fail loudly, not silently
    un-calibrate the plan)."""
    with open(path) as f:
        profile = json.load(f)
    if not isinstance(profile, dict) or "effective" not in profile:
        raise ValueError(f"{path}: not a calibration profile "
                         f"(missing 'effective' rates)")
    if profile.get("version") != CALIBRATION_VERSION:
        raise ValueError(f"{path}: calibration version "
                         f"{profile.get('version')!r} != "
                         f"{CALIBRATION_VERSION}")
    return profile


# --- sample sources ----------------------------------------------------

def _load_jsonl(path: str) -> List[Dict[str, Any]]:
    out = []
    with open(path) as f:
        for line in f:
            line = line.strip()
            if line:
                try:
                    out.append(json.loads(line))
                except json.JSONDecodeError:
                    continue  # the report's count-and-skip contract
    return out


def samples_from_planbench(path: str) -> List[Dict[str, Any]]:
    """(costs, measured) pairs from ``planbench_candidate`` lines —
    requires the per-candidate cost fields (lines without them yield
    no samples)."""
    samples = []
    for rec in _load_jsonl(path):
        if rec.get("metric") != "planbench_candidate":
            continue
        samples.append({
            "flops": rec.get("flops"),
            "bytes_accessed": rec.get("bytes_accessed"),
            "collective_bytes": rec.get("collective_bytes"),
            "measured_ms": rec.get("measured_step_ms_min"),
            "key": rec.get("key"),
        })
    return _valid(samples)


def samples_from_metrics(path: str) -> List[Dict[str, Any]]:
    """(costs, measured) pairs from a run's own metrics JSONL: each
    program's latest ``compile`` record (flops/bytes) joined with its
    latest ``device_time`` record (measured ms per call from the xprof
    attribution)."""
    costs: Dict[str, Dict[str, Any]] = {}
    measured: Dict[str, float] = {}
    for rec in _load_jsonl(path):
        if rec.get("event") == "compile" and rec.get("program"):
            costs[rec["program"]] = rec
        elif (rec.get("event") == "device_time" and rec.get("program")
                and isinstance(rec.get("device_ms_per_call"),
                               (int, float))):
            measured[rec["program"]] = float(rec["device_ms_per_call"])
    samples = []
    for program, ms in measured.items():
        c = costs.get(program)
        if c is None:
            continue
        samples.append({"flops": c.get("flops"),
                        "bytes_accessed": c.get("bytes_accessed"),
                        "collective_bytes": 0.0,
                        "measured_ms": ms, "key": program})
    return _valid(samples)


def main(argv: Optional[Sequence[str]] = None) -> int:
    parser = argparse.ArgumentParser(
        prog="python -m tensorflow_distributed_tpu.analysis.planner"
             ".calibrate",
        description="fit effective device rates from measured step "
                    "times and write an atomic calibration.json the "
                    "planner roofline prefers over its static tables")
    parser.add_argument("--from-planbench", default="",
                        help="planbench_candidate lines with "
                        "per-candidate cost fields")
    parser.add_argument("--from-jsonl", default="",
                        help="run metrics JSONL: compile records "
                        "joined with xprof device_time records")
    parser.add_argument("--platform", default="",
                        help="override the platform tag (default: "
                        "read from the source artifact, else "
                        "'unknown')")
    parser.add_argument("--device-kind", default="",
                        help="override the device-kind tag")
    parser.add_argument("--out", default="calibration.json")
    args = parser.parse_args(argv)
    if bool(args.from_planbench) == bool(args.from_jsonl):
        parser.error("exactly one of --from-planbench / --from-jsonl")
    if args.from_planbench:
        samples = samples_from_planbench(args.from_planbench)
        source = f"planbench:{os.path.basename(args.from_planbench)}"
        tags = next((r for r in _load_jsonl(args.from_planbench)
                     if "platform" in r), {})
        platform = args.platform or tags.get("platform", "unknown")
        devices = int(tags.get("devices", 0) or 0)
    else:
        samples = samples_from_metrics(args.from_jsonl)
        source = f"metrics:{os.path.basename(args.from_jsonl)}"
        platform = args.platform or "unknown"
        devices = 0
    kind = args.device_kind
    if not kind:
        # The live device's kind, when a backend is reachable — the
        # profile must name what it measured.
        try:
            import jax
            kind = getattr(jax.devices()[0], "device_kind", "unknown")
            if not args.platform:
                platform = jax.default_backend()
        except Exception:
            kind = "unknown"
    try:
        fit = fit_rates(samples)
    except ValueError as e:
        print(f"calibrate: {e}", file=sys.stderr)
        return 1
    profile = make_profile(fit, platform, kind, source=source,
                           devices=devices)
    write_calibration(profile, args.out)
    eff = profile["effective"]
    print(f"calibrate: {fit['samples']} samples -> "
          f"eff_flops={eff['peak_flops']:.3g}/s "
          f"eff_hbm={eff['hbm_bw']:.3g}B/s "
          f"eff_ici={'%.3g' % eff['ici_bw'] if eff['ici_bw'] else '-'}"
          f"B/s  median_rel_err={fit['median_abs_rel_err']}")
    print(f"calibrate: wrote {args.out} "
          f"(id {profile['calibration_id']})")
    return 0


if __name__ == "__main__":
    sys.exit(main())
