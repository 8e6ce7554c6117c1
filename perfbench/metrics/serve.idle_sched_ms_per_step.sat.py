"""The saturated cell's reading of serve.idle_sched_ms_per_step: there
the backlog grows to tens of requests, which the scheduler's phases
would show if any of them walked it."""

from harness.loader import load_reader

read = load_reader("serve.idle_sched_ms_per_step")
