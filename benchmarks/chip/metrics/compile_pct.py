"""Trace generation: JAX's compiles, each phase (tracing, lowering, the
backend compile) and each persistent-cache read a ``jax_compile`` span
(``core/obs/spans.py``), as a share of the window."""


def read(layers):
    return layers.share(r"jax_compile")
