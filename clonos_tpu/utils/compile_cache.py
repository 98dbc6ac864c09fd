"""Persistent XLA compilation cache: one directory per process.

The failure path is prewarm-compiled at job start; with this cache a
RESTARTED job pays near-zero for those compiles (the reference's standby
deploy analog survives process restarts), and the first-step executable
:func:`aot_lower_first_step` produces at prewarm makes a rebooted
standby's ``finalize.first-step-recompile`` a cache hit.

:func:`enable_compile_cache` is the only place the directory is chosen.
Entry points call it once at start (``benchmark/run.py``,
``chip_smoke.py``, ``cli run|worker|slotworker``, ``tests/conftest.py``); nothing below
them re-points it. JAX's entry key covers the program, its compile
options and its device assignment, so sharded and unsharded programs
share the directory without colliding.
"""

from __future__ import annotations

import os
import time
from typing import Any, Optional

#: the checkout this package sits in
_REPO_ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


def enable_compile_cache() -> str:
    """Turn JAX's persistent compile cache on and return its directory:
    the one ``JAX_COMPILATION_CACHE_DIR`` names when it is set (JAX reads
    the variable itself — no directory is set in code, so the cache can
    be placed from outside), otherwise the fixed ``<checkout>/.jax_cache``.
    Every compile is persisted, so a second run of the same program adds
    no entry."""
    import jax
    if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        jax.config.update("jax_compilation_cache_dir",
                          os.path.join(_REPO_ROOT, ".jax_cache"))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    return jax.config.jax_compilation_cache_dir


def aot_lower_first_step(executor, metric_group: Optional[Any] = None
                         ) -> Any:
    """Ahead-of-time lower + compile the standby's FIRST-STEP program —
    the block program a rehydrating standby dispatches before anything
    else — so its executable is in the persistent cache (and XLA's
    in-process cache) before any failure happens: the recovery's
    ``finalize.first-step-recompile`` is then a cache hit.

    Lowering uses the executor's live carry avals + shardings (no
    execution, no donation — ``lower`` only traces). Returns the
    compiled executable. A compile error is counted
    (``recovery.aot-lower-failed`` trace instant and, when
    ``metric_group`` is given, the counter of the same name) and
    re-raised: a standby whose first program does not compile cannot
    take over, and prewarm is where that has to surface."""
    from clonos_tpu.obs.trace import get_tracer
    t0 = time.monotonic()
    try:
        carry = executor.carry      # one read: stable vs concurrent swap
        exe = executor._jit_block.lower(
            carry, executor.first_step_inputs()).compile()
    except Exception as err:
        get_tracer().event("recovery.aot-lower-failed",
                           error=repr(err)[:200])
        if metric_group is not None:
            metric_group.counter("recovery.aot-lower-failed").inc()
        raise
    get_tracer().complete("recovery.aot-lower", time.monotonic() - t0)
    return exe
