"""What both runners share: the look for the chip, the device record, the
working directory and the last line."""

from __future__ import annotations

import json
import math
import os
import shutil
import sys
import time
from typing import Any, Dict, List, Optional

from .loader import ROOT, Cell, load_reader

EXIT_NO_CHIP = 4
EXIT_REHEARSED = 5
T_PROCESS_START = time.perf_counter()


def say(msg: str) -> None:
    print(f"[bench] {msg}", flush=True)


def find_devices(chips: int, require_tpu: bool = True) -> Dict[str, Any]:
    """The device record of the result line. Without a TPU, or with fewer
    chips than the cell asks for, the run ends here with no result."""
    import jax

    devs = jax.devices()
    dev = {"platform": devs[0].platform, "kind": devs[0].device_kind,
           "count": chips}
    if require_tpu and dev["platform"] != "tpu":
        print(f"perfbench: JAX found no TPU (platform "
              f"{dev['platform']!r}); the benchmark measures the chip and "
              f"does not fall back", file=sys.stderr, flush=True)
        sys.exit(EXIT_NO_CHIP)
    if len(devs) < chips:
        print(f"perfbench: the cell needs {chips} chips, JAX reports "
              f"{len(devs)}", file=sys.stderr, flush=True)
        sys.exit(EXIT_NO_CHIP)
    if len(devs) > chips:
        # One chip's worth of work on a bigger host: hide the rest from
        # the program's mesh construction (parallel/mesh.py's mask).
        os.environ["TFD_DEVICE_MASK"] = str(len(devs) - chips)
    return dev


def memory_peak_bytes(chips: int) -> Optional[int]:
    """Peak bytes in use on the fullest of the chips the cell uses."""
    import jax

    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use")
             for d in jax.devices()[:chips]]
    peaks = [p for p in peaks if p is not None]
    return max(peaks) if peaks else None


def work_dir(cell_name: str) -> str:
    """A scratch directory inside the checkout (artifacts of one run:
    request file, metrics JSONL, trace), emptied first."""
    path = os.path.join(ROOT, ".cache", "perfbench", cell_name)
    shutil.rmtree(path, ignore_errors=True)
    os.makedirs(path)
    return path


class Ctx:
    """What a per-layer reader may look at."""

    def __init__(self, **kw):
        self.trace = None
        self.records: List[Dict[str, Any]] = []
        self.__dict__.update(kw)


def read_jsonl(path: str) -> List[Dict[str, Any]]:
    out = []
    if os.path.exists(path):
        with open(path) as f:
            for line in f:
                line = line.strip()
                if line:
                    out.append(json.loads(line))
    return out


def root_key(seed: int):
    """The key a model's ``make_params`` gets for ``--seed``."""
    import jax
    return jax.random.PRNGKey(int(seed) % 2 ** 32)


def peaks_of(dev: Dict[str, Any]):
    """The chip's published peaks, or None off the chip (a rehearsal)."""
    from . import peaks
    return peaks.peaks_for(dev["kind"]) if dev["platform"] == "tpu" else None


def read_capture(log_dir: str):
    """Load the run's profiler capture and say what it holds."""
    from . import trace as trace_mod
    t0 = time.perf_counter()
    tr = trace_mod.load_xplane(trace_mod.find_xplane(log_dir))
    say(f"trace: {sum(len(d['ops']) for d in tr.devices.values())} device "
        f"ops on {len(tr.devices)} device(s), {len(tr.host)} harness spans, "
        f"window {tr.window_s:.3f}s, read in {time.perf_counter() - t0:.1f}s")
    return tr


def traced_result(cell: Cell, ctx: Ctx, device: Dict[str, Any]):
    """What a ``--trace 1`` run reports from ``ctx.trace``: the device's
    busy time, the breakdown, and every per-layer metric of the cell whose
    reader finds something to read. Returns (metrics, breakdown)."""
    from . import trace as trace_mod
    tr = ctx.trace
    device.update(busy_s=trace_mod.busy_s(tr), window_s=tr.window_s)
    breakdown = {"device_ops": trace_mod.top_ops(tr),
                 "idle_gaps": trace_mod.idle_gaps(tr)}
    metrics: Dict[str, Dict[str, Any]] = {}
    for m in cell.per_layer():
        value = load_reader(m["name"], cell.root)(ctx)
        if value is not None:
            metrics[m["name"]] = {"value": value, "unit": m["unit"]}
    return metrics, breakdown


class Compared:
    """Every number ``correct`` compares, beside its limit: printed as it
    is compared and kept, in order, for the result's line (``rows``) and
    the run's last lines on standard error (``lines``)."""

    def __init__(self):
        self.rows: Dict[str, Dict[str, Any]] = {}
        self.lines: List[str] = []

    def __call__(self, name: str, value: float, limit: float, ok: bool,
                 note: str = "") -> bool:
        line = (f"{name} = {value:.6g} (limit {limit:.6g}) "
                f"{'ok' if ok else 'FAILED'}")
        say(f"correct: {line}{' ' + note if note else ''}")
        self.lines.append(f"correct: {line}")
        # a NaN would make the result's line no JSON
        self.rows[name] = {
            "value": float(value) if math.isfinite(value) else str(value),
            "limit": float(limit), "ok": bool(ok)}
        return ok


def result_line(correct: bool, attempted: int, failed: int,
                metrics: Dict[str, Dict[str, Any]], device: Dict[str, Any],
                breakdown: Optional[Dict[str, List]] = None,
                compared: Optional[Dict[str, Dict[str, Any]]] = None) -> str:
    out = {"correct": bool(correct), "attempted": int(attempted),
           "failed": int(failed), "metrics": metrics, "device": device}
    if breakdown is not None:
        out["breakdown"] = breakdown
    if compared is not None:
        out["compared"] = compared       # last: the end of the line is kept
    return json.dumps(out)
