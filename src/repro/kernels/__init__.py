"""Pallas TPU kernels for the perf-critical hot spots.

    kway_probe      — batched set probe + victim select (the paper's O(k) scan)
    replay          — trace-resident replay megakernel: a whole chunked trace
                      in ONE pallas_call with the cache state pinned in VMEM
    paged_attention — flash-decode GQA over the K-way-managed paged KV pool
    ops             — public jit'd wrappers over the kernels
    ref             — pure-jnp oracles for allclose validation

``interpret()`` below is the one place that decides how the kernels run;
every kernel entry point's ``interpret=None`` default defers to it.
"""


def interpret() -> bool:
    """Pallas kernels compile with Mosaic on a TPU and run in the Pallas
    interpreter on any other platform (the CPU tests).  Nothing falls back
    from one to the other at run time."""
    import jax
    return jax.default_backend() != "tpu"
