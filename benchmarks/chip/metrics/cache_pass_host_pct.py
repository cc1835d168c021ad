"""Cache passes: the host's part, grouping the stream by set with its
padding and scattering the hits back to stream order
(``cache_pass.group``/``cache_pass.scatter`` spans, ``memsim/engine.py``
and ``kernels/cache_sim/ops.py``), as a share of the window."""


def read(layers):
    return layers.share(r"cache_pass\.(group|scatter)")
