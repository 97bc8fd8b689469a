"""Reader ``trace_collectives``: time of the collective operations
(all-reduce, all-gather, reduce-scatter, collective-permute, all-to-all)
over the device's busy time, percent; with ``exposed`` only the part
during which no other operation ran beside them."""


def read(evidence, exposed: bool = False):
    trace = evidence.trace
    if trace is None:
        return None
    busy = trace.busy_s()
    if not busy:
        return None
    coll = trace.collectives()
    return 100.0 * coll["exposed_seconds" if exposed else "seconds"] / busy
