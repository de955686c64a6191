"""The window's fits as the program traced them.

A fit called with ``trace="full"`` appends one line to the
``api.fit.traces`` event log of ``repro.obs.REGISTRY``: its wall, its
counters (``h2d_bytes``, ``d2h_bytes``, ``programs_traced``) and the
seconds of each span name. The window's fits are the last lines, one
each, after the warm-up fit's. A program without that log gives nothing.
"""

LOG = "api.fit.traces"


def per_fit(run, value):
    """Mean over the window's fits of ``value(line)``; None where the
    program keeps no such log, or ``value`` finds nothing in a line."""
    try:
        from repro.obs.metrics import REGISTRY
        lines = REGISTRY.read(LOG)[LOG]["events"]
    except (ImportError, KeyError):
        return None
    n = len(run.fits)
    if len(lines) < n:
        return None
    values = [value(line) for line in lines[-n:]]
    if any(v is None for v in values):
        return None
    return sum(values) / n


def span_s(line, *names):
    """Seconds of the named spans in one fit's line; None if it has none."""
    found = [line["span_s"][s] for s in names if s in line["span_s"]]
    return sum(found) if found else None
