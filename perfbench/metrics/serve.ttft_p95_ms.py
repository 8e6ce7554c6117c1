"""Layer: serve driver. The tail the steady cell's users feel: nearest-rank
p95 of first-token time less due time, over the requests that got their
first token before the profiler capture started (the capture's start and
stop stall the scheduler). It swings by about a tenth from run to run on
the same schedule, more than a bound may be (PERF.md section 2), so it
stands here beside the median that is judged."""

from harness import stats


def read(ctx):
    ttft = ctx.ttft_ms_before_capture
    if not ttft:
        return None
    ctx.say(f"serve.ttft_p95_ms over {len(ttft)} requests served before "
            f"the capture started at {ctx.cut_s:.1f}s")
    return stats.percentile(ttft, 95)
