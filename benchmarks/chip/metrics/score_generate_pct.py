"""Prefetcher scoring on the host: generating each prefetcher's stream
(``score.generate[<prefetcher>]`` spans, ``core/experiment.py``), as a
share of the window."""


def read(layers):
    return layers.share(r"score\.generate\[.*\]")
