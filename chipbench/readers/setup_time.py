"""Reader ``setup_time``: seconds from process start to the first timed
instant -- imports, the compile or the cache load, parameters, warm-up,
the reference check at set-up and the untimed lead-in."""


def read(evidence):
    return evidence.setup_s
