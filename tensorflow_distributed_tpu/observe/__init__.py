"""Unified observability: metrics registry, step-time breakdown, MFU
accounting, Chrome-trace spans, and goodput.

The reference repo's only observability was bare ``print()``
timestamps and a hand-maintained 6-line ``performance`` file; this
package turns every run into structured, comparable data:

- :mod:`observe.registry` — one emission path, pluggable sinks
  (stdout pretty-printer, JSONL, CSV), chief-only emission, host tags;
- :mod:`observe.steptime` — per-step data-wait / dispatch / device
  breakdown with rolling p50/p95;
- :mod:`observe.mfu` — model-FLOPs estimates per family and
  tokens/s / imgs/s / MFU accounting;
- :mod:`observe.trace` — :class:`HostSpans`, the one span seam (each
  ``tfd.*`` host span goes to the profiler capture, the Chrome trace
  and the always-on ``phase_ms`` totals under one name), and the
  pure-Python Chrome-trace (Perfetto) file writer;
- :mod:`observe.goodput` — productive vs. restore/drain/blocked time;
- :mod:`observe.device` — compiled-program registry: every jit site's
  cost_analysis/memory_analysis (flops, bytes, peak-HBM estimate,
  donated bytes) + lower/compile wall time as ``compile`` records;
- :mod:`observe.health` — on-device per-layer training vitals (grad
  norm, update-to-param ratio, param RMS, activation-RMS taps),
  cadence-gated inside the jitted step;
- :mod:`observe.serve_trace` — per-request async-span trees for
  ``mode=serve`` (one Perfetto file, balanced even across a
  supervised restart);
- :mod:`observe.slo` — live SLO burn-rate monitor: declared
  percentile targets, fast/slow windows on the decode-step clock,
  ``slo_alert``/``slo_ok`` events with error-budget accounting;
- :mod:`observe.anomaly` — online anomaly detection: streaming
  MAD/median/slope detectors over the already-fetched log-cadence
  values (train) and the decode-step clock (serve), ``anomaly``
  records + the live incident state snapshots export;
- :mod:`observe.flightrec` — crash flight recorder: bounded record
  ring, fsync'd snapshots (SIGKILL-durable), postmortem bundles on
  trappable deaths;
- :mod:`observe.postmortem` — ``python -m ...observe.postmortem
  <bundle>``: timeline + likely-cause incident report from a bundle;
- :mod:`observe.hub` — the :class:`Observatory` the train loop drives
  and the :class:`ServeObservatory` bundle serve/run.py drives;
- :mod:`observe.xprof` — device-time attribution: parse the
  profiler's Perfetto export into per-program ``device_time`` records
  (measured device wall + collective families vs roofline predicted);
- :mod:`observe.report` — ``python -m ...observe.report metrics.jsonl
  [more.jsonl ...]`` summarizer (multi-host streams merge, per-host
  sections).

The full record schema every module emits is documented in RECORDS.md.
"""

from tensorflow_distributed_tpu.observe.goodput import (  # noqa: F401
    GoodputCounter)
from tensorflow_distributed_tpu.observe.hub import Observatory  # noqa: F401
from tensorflow_distributed_tpu.observe.mfu import (  # noqa: F401
    PEAK_BF16_FLOPS, ThroughputAccountant, device_peak_flops,
    flops_per_item, flops_per_token)
from tensorflow_distributed_tpu.observe.registry import (  # noqa: F401
    CsvSink, JsonlSink, MetricsRegistry, StdoutSink, config_hash,
    host_tags)
from tensorflow_distributed_tpu.observe.steptime import (  # noqa: F401
    StepTimeBreakdown)
from tensorflow_distributed_tpu.observe.trace import (  # noqa: F401
    ChromeTracer, HostSpans, load_trace)
