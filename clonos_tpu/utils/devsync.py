"""Device-completion sync for timing code.

JAX dispatch is asynchronous: a timing that does not wait for the result
measures the enqueue. ``jax.block_until_ready`` returns only when the
work is done (``chip_smoke.py`` shows it once per run, against a
device→host read of the same result), so that is the whole of it.
"""

from __future__ import annotations


def device_sync(tree) -> None:
    """Block until every array in ``tree`` is computed."""
    import jax
    jax.block_until_ready(tree)
