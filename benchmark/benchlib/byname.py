"""Files the harness finds by a name in ``BENCHMARK.json`` or in a
configuration: ``readers/<metric>.py``, ``topologies/<topology>/job.py``
and ``reference.py``. A later PR adds one by adding its file."""

from __future__ import annotations

import importlib.util
import os
import re
from types import ModuleType


def module_at(path: str) -> ModuleType:
    """The Python file at ``path`` as a module of its own; a missing file
    is an error that names it."""
    if not os.path.isfile(path):
        raise SystemExit(f"benchmark: no file {path}")
    spec = importlib.util.spec_from_file_location(
        "bench_" + re.sub(r"\W", "_", os.path.basename(os.path.dirname(path))
                          + "_" + os.path.basename(path)[:-3]), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod
