"""Overhear the stats of an engine's spans from a test: those a span opens with
and those it is given later (``set_metadata``), as one dict a span."""


class _Overheard:
    def __init__(self, span, stats, probe):
        self._span, self._stats, self._probe = span, stats, probe

    def __enter__(self):
        self._span.__enter__()
        return self

    def __exit__(self, *exc):
        return self._span.__exit__(*exc)

    def set_metadata(self, **more):
        self._stats.update(more)
        if self._probe is not None:
            self._stats.setdefault("probe", self._probe())
        self._span.set_metadata(**more)


def overhear(engine, name, probe=None):
    """The list that fills with the stats of every ``name`` span ``engine``
    opens from now on; ``probe()``, if given, is read when the span is first
    given stats after it opened and kept under ``"probe"``."""
    seen, span = [], engine._span

    def spy(span_name, **stats):
        sp = span(span_name, **stats)
        if span_name != name:
            return sp
        seen.append(dict(stats))
        return _Overheard(sp, seen[-1], probe)

    engine._span = spy
    return seen
