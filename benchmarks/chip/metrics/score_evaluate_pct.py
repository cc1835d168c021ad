"""Prefetcher scoring on the host: ``evaluate`` of each scored stream
(``score.evaluate`` spans, ``core/experiment.py``), as a share of the
window."""


def read(layers):
    return layers.share(r"score\.evaluate")
