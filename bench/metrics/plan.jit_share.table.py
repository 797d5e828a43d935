"""Percent of the window spent tracing, lowering and compiling (or loading
from the persistent cache) the query programs: JAX's monitoring spans of
``collect()``'s fresh ``jax.jit``, read on the host clock."""


def read(run):
    return run.share_of_window([(s, e) for _, s, e in run.clock.spans],
                               wall=True)
