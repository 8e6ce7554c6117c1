"""Profiling subsystem: step-windowed traces produce XPlane artifacts."""

import glob
import os

import jax
import jax.numpy as jnp

from tensorflow_distributed_tpu.observe.trace import HostSpans
from tensorflow_distributed_tpu.utils.profiling import (
    StepProfiler, trace)


def _work():
    x = jnp.ones((64, 64))
    jax.block_until_ready(jnp.dot(x, x))


def test_step_profiler_window(tmp_path):
    p = StepProfiler(log_dir=str(tmp_path), start_step=2, num_steps=2)
    spans = HostSpans()
    for step in range(1, 6):
        p.observe(step)
        with spans.span("test.work", step=step):
            _work()
    p.stop()
    files = glob.glob(os.path.join(str(tmp_path), "**", "*.xplane.pb"),
                      recursive=True)
    assert files, "no trace artifact written"
    # The seam's span is in the capture, on the host plane, only for
    # the steps the window covered.
    from jax.profiler import ProfileData
    names = [e.name for plane in ProfileData.from_file(files[-1]).planes
             if plane.name.startswith("/host:")
             for line in plane.lines for e in line.events]
    assert names.count("tfd.test.work") == 2
    assert spans.totals.as_dict()["tfd.test.work"]["count"] == 5


def test_step_profiler_disabled_is_noop(tmp_path):
    p = StepProfiler(log_dir="")
    for step in range(5):
        p.observe(step)
    p.stop()
    assert not os.listdir(tmp_path)


def test_trace_span(tmp_path):
    with trace(str(tmp_path)):
        _work()
    files = glob.glob(os.path.join(str(tmp_path), "**", "*.xplane.pb"),
                      recursive=True)
    assert files
