"""What the readers of the program's span ring share (``trace_s``,
``lower_s``, ``xla_s``, ``dispatch_self_ms``, ``loader_busy_pct``,
``loader_stage_ms``): the ring of ``horovod_tpu.timeline``, whose clock,
``time.perf_counter_ns()``, is the one ``run.py`` stamps
``record["window"]`` with, so a window is selected with no conversion.
A program that has no ring (a parent commit) gives every reader None."""

STEP_BUILD = ("step/lower", "step/first_call")


def kept_since(t_ns=None):
    """The ring's spans, oldest first; None where the program has no
    ring, or where the ring has dropped spans and what is left may not
    hold every span that ended after ``t_ns`` (after the process's start
    for None): spans are kept in the order they ended, the oldest
    dropped first."""
    try:
        from horovod_tpu import timeline
    except ImportError:
        return None
    ring = getattr(timeline, "ring", None)
    if ring is None:
        return None
    spans = ring.snapshot()
    if ring.dropped and (t_ns is None or not spans
                         or spans[0].end_ns >= t_ns):
        return None
    return spans


def window_ns(record):
    """The measured window on the ring's clock: its opening and the last
    step seen complete."""
    w = record["window"]
    if not w["boundaries"]:
        return None
    return int(w["t_open"] * 1e9), int(w["boundaries"][-1] * 1e9)


def _window_and_spans(record):
    window = window_ns(record)
    spans = kept_since(window[0]) if window else None
    return (window, spans) if spans is not None else None


def in_window(record, name):
    """Spans called ``name`` that lie whole inside the measured window,
    in the order they began; None as :func:`kept_since`."""
    found = _window_and_spans(record)
    if found is None:
        return None
    (t_open, t_close), spans = found
    return sorted((s for s in spans if s.name == name
                   and s.start_ns >= t_open and s.end_ns <= t_close),
                  key=lambda s: s.start_ns)


def clipped_to_window(record, names):
    """Spans called one of ``names`` as far as they lie inside the
    measured window, cut at its two ends; None as :func:`kept_since`."""
    found = _window_and_spans(record)
    if found is None:
        return None
    (t_open, t_close), spans = found
    return [s._replace(start_ns=max(s.start_ns, t_open),
                       end_ns=min(s.end_ns, t_close))
            for s in spans if s.name in names
            and s.end_ns > t_open and s.start_ns < t_close]


def during_setup(record):
    """Every span that ended before the window opened; None as
    :func:`kept_since`."""
    spans = kept_since()
    if spans is None:
        return None
    t_open = int(record["window"]["t_open"] * 1e9)
    return [s for s in spans if s.end_ns <= t_open]


def under(spans, names):
    """Those of ``spans`` with an ancestor called one of ``names``."""
    by_id = {s.id: s for s in spans}

    def has_ancestor(s):
        while s.parent in by_id:
            s = by_id[s.parent]
            if s.name in names:
                return True
        return False

    return [s for s in spans if has_ancestor(s)]


def covered_s(spans):
    """Seconds the spans cover together: the union of their intervals,
    so a span inside another adds nothing."""
    total = reached = 0
    for s in sorted(spans, key=lambda s: s.start_ns):
        start = max(s.start_ns, reached)
        if s.end_ns > start:
            total += s.end_ns - start
            reached = s.end_ns
    return total / 1e9
