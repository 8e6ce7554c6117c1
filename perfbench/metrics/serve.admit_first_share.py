"""Layer: serve engine. Of the admissions of the run, the share whose
prefill was dispatched with NO decode step queued behind the one running
(an idle engine's admissions too), in percent: the program's own counts
(``serve_summary.admits_first / admissions``). Such a prefill is the next
thing the device does: the request's first token waits for what is left
of the running step and not for a whole step more. The rest came due
after the engine had launched the step ahead (the last margin of a step,
or a slot that a step's retire had just freed). A program without the
counter (the parent of the PR that added it, PR 45) gives nothing to
read."""

from harness import decode_parts as D


def read(ctx):
    s = D.summary_of(ctx.records)
    if not s or s.get("admits_first") is None or not s.get("admissions"):
        return None
    return 100.0 * s["admits_first"] / s["admissions"]
