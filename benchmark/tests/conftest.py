"""The benchmark's own tests: a CPU rehearsal of the harness at a tiny
size, and the yardstick's arithmetic on hand-made inputs. Not collected
by the repo's tier-1 run (``pytest tests/``); run them with

    python -m pytest benchmark/tests -q

Four forced host devices stand in for the 2x2 mesh. A CPU run gives
counts and right answers, never a time or a rate.
"""

import json
import os
import sys

import pytest

os.environ.setdefault("JAX_PLATFORMS", "cpu")
if "xla_force_host_platform_device_count" not in os.environ.get(
        "XLA_FLAGS", ""):
    os.environ["XLA_FLAGS"] = (
        os.environ.get("XLA_FLAGS", "")
        + " --xla_force_host_platform_device_count=4").strip()

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
ROOT = os.path.dirname(BENCH)
for p in (BENCH, ROOT):
    if p not in sys.path:
        sys.path.insert(0, p)

#: configuration of BENCHMARK.json -> its tiny stand-in under tests/tiny
TINY = {"kafka-window-64": "tiny-kafka", "allround-32": "tiny-allround",
        "allround-64": "tiny-allround-x4"}


@pytest.fixture(scope="session")
def tiny_bench(tmp_path_factory) -> str:
    """The repo's BENCHMARK.json with every configuration and traffic
    mix swapped for its tiny stand-in: same cells, same metrics, same
    readers."""
    bench = json.load(open(os.path.join(ROOT, "BENCHMARK.json")))
    tiny = os.path.join(HERE, "tiny", "bench")
    bench["paths"] = [tiny]
    for c in bench["configs"]:
        c["file"] = os.path.join(tiny, "configs", TINY[c["name"]] + ".json")
    path = tmp_path_factory.mktemp("bench") / "BENCHMARK.json"
    path.write_text(json.dumps(bench))
    return str(path)
