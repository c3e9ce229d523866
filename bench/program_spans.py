"""The system's own spans and events of the traced window.

``repro.obs.trace.captured()`` holds every span and event that opened
while a ``jax.profiler`` capture ran in the process: with ``--trace 1``
that is exactly the harness's traced window.  A program without that
capture yields no records, and the readers that use this module then
report nothing.
"""


def records(name: str) -> list:
    """The captured records called ``name``, oldest first."""
    from repro.obs import trace
    captured = getattr(trace, "captured", None)
    if captured is None:
        return []
    return [r for r in captured() if r.get("name") == name]


def mean_ms(name: str):
    """Mean duration of the captured spans called ``name``, in ms; None
    when there are none."""
    d = [r["dur"] / 1e3 for r in records(name) if r.get("ph") == "X"]
    return sum(d) / len(d) if d else None
