"""Prefetcher scoring on the host: building the merged L2 stream of
demand and prefetches (``prefetch.merge`` spans, ``core/experiment.py``
and ``memsim/hierarchy.py``; the next-line baseline's merge counts too),
as a share of the window."""


def read(layers):
    return layers.share(r"prefetch\.merge")
