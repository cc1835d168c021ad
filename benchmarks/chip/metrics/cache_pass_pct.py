"""Cache passes (``memsim/engine.py``, ``memsim/fused.py``,
``memsim/hierarchy.py``): host grouping, the device pass and the copy
back, as a share of the window."""


def read(layers):
    return layers.share(r"cache_pass\[.*\]")
