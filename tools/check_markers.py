#!/usr/bin/env python
"""Shim over ``clonos_tpu.lint.markers``: the marker registry and the scan both moved
into the lint package as the ``markers`` rule, where
``clonos_tpu lint tests/`` and tests/conftest.py share them. This file
keeps the historical entry point — ``python tools/check_markers.py``
still exits 1 listing violations — and the historical import surface
(REGISTERED_MARKERS / BUILTIN_MARKERS / check).
"""

import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
from clonos_tpu.lint.markers import (BUILTIN_MARKERS,     # noqa: E402,F401
                                     REGISTERED_MARKERS, check)


def main(argv=None):
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    violations = check(os.path.join(root, "tests"))
    for v in violations:
        print(v, file=sys.stderr)
    if violations:
        print(f"{len(violations)} unregistered marker use(s); register "
              f"in clonos_tpu/lint/markers.py:REGISTERED_MARKERS",
              file=sys.stderr)
        return 1
    print(f"markers ok ({len(REGISTERED_MARKERS)} registered)")
    return 0


if __name__ == "__main__":
    sys.exit(main())
