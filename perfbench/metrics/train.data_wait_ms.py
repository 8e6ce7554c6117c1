"""Layer: train loop. Mean ``data_ms`` (the loop's wait for its next
batch) of the program's step records that fall inside the window."""


def read(ctx):
    lo = ctx.window["first_step"]
    hi = lo + ctx.window["steps"]
    waits = [r["data_ms"] for r in ctx.records
             if r.get("event") == "step" and lo < r.get("step", 0) <= hi
             and r.get("data_ms") is not None]
    return sum(waits) / len(waits) if waits else None
