"""Host->device input prefetching.

``prefetch_to_device`` walks an iterator of (pytrees of) host arrays
and keeps ``size`` items' device transfers in flight ahead of the
consumer: JAX's ``device_put`` is asynchronous, so batch ``i+1``'s
host->device copy overlaps batch ``i``'s compute instead of serializing
in front of it. This is the input-pipeline half of keeping the chip
busy — the per-batch dispatch paths (conv sync-average training, the
async worker's parity loop) otherwise pay a blocking transfer at the
top of every step.

The reference delegates all data movement to Spark (RDD partitions
materialize as numpy inside the executor, ``elephas/worker.py:36-38``);
on TPU the equivalent concern is the host->HBM edge, and overlap is the
idiomatic answer.
"""
from collections import deque
from typing import Iterable, Iterator, Optional

import jax

__all__ = ["prefetch_to_device"]


def prefetch_to_device(iterable: Iterable, size: int = 2,
                       sharding: Optional[object] = None) -> Iterator:
    """Yield items of ``iterable`` (pytrees of host arrays) as device
    arrays, keeping up to ``size`` transfers in flight ahead of the
    consumer. Order is preserved. ``sharding`` (e.g. a
    ``NamedSharding``) is applied to every leaf when given; default
    placement otherwise. ``size=0`` disables lookahead (plain
    device_put per item)."""
    if size < 0:
        raise ValueError("size must be >= 0")

    def put(item):
        if sharding is None:
            return jax.device_put(item)
        return jax.device_put(item, sharding)

    queue = deque()
    for item in iterable:
        queue.append(put(item))
        if len(queue) > size:
            yield queue.popleft()
    while queue:
        yield queue.popleft()
