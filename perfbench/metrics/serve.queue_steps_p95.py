"""Layer: serve driver. 95th percentile over the run's ``serve_request``
records of ``queue_steps``: decode steps a request endured while it could
have been admitted. A count made by the program. Only requests that got
their first token before the profiler capture started are counted: the
capture's start and stop stall the scheduler."""

from harness import stats


def read(ctx):
    q = [r["queue_steps"] for r in ctx.records
         if r.get("event") == "serve_request"
         and r.get("queue_steps") is not None
         and r.get("t_first_s", 0.0) <= ctx.cut_s]
    return stats.percentile(q, 95) if q else None
