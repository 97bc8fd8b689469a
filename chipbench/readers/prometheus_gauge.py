"""Reader ``prometheus_gauge``: gauges polled over ``/metrics`` during
the window. ``series`` is one name or a list whose values are added at
each poll (free blocks + blocks the prefix cache would give back =
blocks an admission can have); ``reduce`` is ``min``, ``max`` or
``mean`` over the polls. With ``of_total`` (a number, or ``engine.<key>``
looked up in the configuration's engine sizes: ``engine.paged.0`` is the
pool's block count) the result is a percentage of it, of what is left
when ``used`` is true.
"""
from chipbench.evidence import parse_prometheus


def _total(evidence, of_total):
    if isinstance(of_total, (int, float)):
        return float(of_total)
    node = evidence.engine_sizes
    for key in of_total.split(".")[1:]:
        node = node[int(key)] if isinstance(node, list) else node[key]
    return float(node)


def read(evidence, series, reduce: str = "mean", of_total=None,
         used: bool = False):
    names = [series] if isinstance(series, str) else list(series)
    start, end = evidence.window or (None, None)
    values = []
    for t, text in evidence.polls:
        if not start <= t <= end:
            continue
        parsed = parse_prometheus(text)
        if all(name in parsed for name in names):
            values.append(sum(parsed[name] for name in names))
    if not values:
        return None
    value = {"min": min, "max": max,
             "mean": lambda v: sum(v) / len(v)}[reduce](values)
    if of_total is None:
        return value
    share = value / _total(evidence, of_total)
    return 100.0 * (1.0 - share if used else share)
