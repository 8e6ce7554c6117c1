"""Layer: serve engine. The decode steps the run retired, the program's
own count (``serve_summary.decode_steps``). A test's reader: it stands for
the per-layer metric a later PR brings with its cell
(``conftest.py::add_second_model``)."""

from harness import decode_parts as D


def read(ctx):
    s = D.summary_of(ctx.records)
    return float(s["decode_steps"]) if s and s.get("decode_steps") else None
