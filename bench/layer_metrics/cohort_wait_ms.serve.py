"""Mean time a cohort waited in the daemon's engine before its
preparation began, in ms: each ``cohort.wait`` span's ``queued_ms`` (from
submission to a dispatch thread taking it) plus the span itself (waiting
for a slot of the in-flight window), from the system's own spans."""

from bench import program_spans


def read(ctx):
    waits = [r["args"].get("queued_ms", 0.0) + r["dur"] / 1e3
             for r in program_spans.records("cohort.wait")
             if r.get("ph") == "X"]
    return sum(waits) / len(waits) if waits else None
