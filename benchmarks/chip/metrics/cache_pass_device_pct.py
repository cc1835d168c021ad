"""Cache passes: the device round trip, transfer in, the kernel and the
copy of the hits back (``cache_pass.device`` spans, ``memsim/engine.py``
and ``kernels/cache_sim/ops.py``), as a share of the window."""


def read(layers):
    return layers.share(r"cache_pass\.device")
