"""Prefetcher scoring on the host (``core/prefetchers/``, ``core/amc/``,
``memsim/metrics.py``): the ``score`` spans less the cache passes nested
in them, as a share of the window."""


def read(layers):
    return layers.share(r"score", r"cache_pass\[.*\]")
