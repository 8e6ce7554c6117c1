"""Layer: serve engine. Of the decode steps the run retired, the share
the engine launched from the previous step's tokens on the device,
before it had fetched them, in percent: the program's own count
(``serve_summary.steps_ahead / decode_steps``). Such a step starts the
moment the one before it ends; the rest waited for the host (the first,
the one after an idle engine). A program without the counter (the parent
of the PR that added it) gives nothing to read."""

from harness import decode_parts as D


def read(ctx):
    s = D.summary_of(ctx.records)
    if not s or s.get("steps_ahead") is None or not s.get("decode_steps"):
        return None
    return 100.0 * s["steps_ahead"] / s["decode_steps"]
