"""Reader ``prometheus_delta``: the change of the program's own series
over the window, from two scrapes of ``/metrics``.

``numerator`` / ``denominator`` name series (a histogram's
``<name>_sum`` and ``<name>_count`` are series too); the result is
``scale * delta(numerator) / delta(denominator)``, or ``scale *
delta(numerator)`` with no denominator.
"""
from chipbench.evidence import parse_prometheus


def read(evidence, numerator: str, denominator: str = None,
         scale: float = 1.0):
    if evidence.prom_start is None or evidence.prom_end is None:
        return None
    before = parse_prometheus(evidence.prom_start)
    after = parse_prometheus(evidence.prom_end)
    if numerator not in after:
        return None
    top = after[numerator] - before.get(numerator, 0.0)
    if denominator is None:
        return scale * top
    if denominator not in after:
        return None
    bottom = after[denominator] - before.get(denominator, 0.0)
    return scale * top / bottom if bottom else None
