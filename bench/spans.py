"""The program's host spans, as it tallies them over one profiler capture.

The program observes the wall seconds of each host span it opens while a
profiler session records into ``trace_span_seconds{span=<name>}`` in its
process-global registry (``repro.obs``). The serve driver opens the session
around the traced window only, so in ``bench/run.py``'s process the tallies
cover that window. A program without those tallies reads as none.
"""

SPAN_METRIC = "trace_span_seconds"


def totals(name: str):
    """(seconds, count) of span ``name`` in the registry; (0.0, 0) where
    the program tallies no such span."""
    from repro.obs import metrics

    fam = metrics.get_registry().snapshot().get(SPAN_METRIC)
    for v in (fam or {}).get("values", []):
        if v["labels"].get("span") == name:
            return v["sum"], v["count"]
    return 0.0, 0


def mean_us(name: str):
    """Mean wall microseconds of span ``name``, or None with no span."""
    s, n = totals(name)
    return s / n * 1e6 if n else None
