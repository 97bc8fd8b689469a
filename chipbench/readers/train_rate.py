"""Reader ``train_rate``: tokens trained per second over the whole mesh,
on the host's clock: the harness stamps the end of every epoch (the
program ends an epoch with a fetch that blocks, so the stamp is real),
drops the first epoch of the measured ``fit`` (it holds the re-trace and
the cache lookup) and divides the tokens of the others by the time
between the first stamp and the last."""


def read(evidence):
    ends = evidence.epoch_ends
    if len(ends) < 2 or not evidence.tokens_per_epoch:
        return None
    return (len(ends) - 1) * evidence.tokens_per_epoch / (ends[-1] - ends[0])
