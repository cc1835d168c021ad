"""Prefetcher scoring on the host: classifying each prefetch and demand
event after the passes (``prefetch.classify`` spans,
``memsim/hierarchy.py``; the next-line baseline's counts too), as a share
of the window."""


def read(layers):
    return layers.share(r"prefetch\.classify")
