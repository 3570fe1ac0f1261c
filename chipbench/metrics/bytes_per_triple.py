"""bytes_per_triple: bytes of the live device arrays at the window's
open (JAX's own count, ``jax.live_arrays()``), over the explicit triples
loaded."""


def read(run):
    return run.device_bytes / run.n_explicit
