"""Reader ``trace_idle``: the share of the traced window in which no
operation ran on the device (1 - union of the ``XLA Ops`` intervals over
the window, averaged over the chips used), in percent."""


def read(evidence):
    trace = evidence.trace
    if trace is None:
        return None
    share = trace.idle_share()
    return None if share is None else 100.0 * share
