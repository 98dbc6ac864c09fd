"""Device, open-loop cells: ``idle_unattributed_pct`` where the end-to-end
metric is commit latency (the harness's sleep until an epoch is due
counts as attributed, under ``bench:wait_due``)."""

import os

from benchlib.byname import module_at

read = module_at(os.path.join(os.path.dirname(os.path.abspath(__file__)),
                              "idle_unattributed_pct.py")).read
