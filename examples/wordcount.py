"""SocketWindowWordCount, TPU-native.

The reference's demo job (flink-examples-streaming
.../socket/SocketWindowWordCount.java, and the causal-services variant in
the reference README.md:46-77): words from a socket (or a synthetic
generator), keyed tumbling-window counts, printed at the sink.

Run:
    python -m clonos_tpu run examples.wordcount:build_job --epochs 4
    python examples/wordcount.py            # self-driving demo with a
                                            # mid-run failure + recovery
"""

import os
import sys

sys.path.insert(0, os.path.dirname(
    os.path.dirname(os.path.abspath(__file__))))

from clonos_tpu.api.environment import StreamEnvironment

VOCAB = 1000
WINDOW_MS = 500


def build_job():
    env = StreamEnvironment(name="socket-window-wordcount",
                            num_key_groups=64)
    (env.synthetic_source(vocab=VOCAB, batch_size=64, parallelism=4,
                          name="words")
        .key_by()
        .window_count(num_keys=VOCAB, window_size=WINDOW_MS, name="window")
        # the window emits a slot a word: an edge of the default 256
        # would drop words 256-999, and a drop stops the run
        .sink(name="print", capacity=VOCAB))
    return env.build()


def build_socket_job(host: str = "localhost", port: int = 9999):
    """The literal socket variant: feed lines 'key[:value]' over TCP."""
    env = StreamEnvironment(name="socket-window-wordcount",
                            num_key_groups=64)
    (env.host_source(batch_size=64, parallelism=1, name="socket")
        .key_by()
        .window_count(num_keys=VOCAB, window_size=WINDOW_MS, name="window")
        .sink(name="print", capacity=VOCAB))
    return env.build()


def main():
    import numpy as np
    from clonos_tpu.runtime.cluster import ClusterRunner

    runner = ClusterRunner(build_job(), steps_per_epoch=8)
    print("running 2 epochs + a few mid-epoch steps...")
    runner.run_epoch()
    runner.run_epoch()
    for _ in range(5):                   # mid-epoch: the failure loses
        runner.step()                    # un-checkpointed work to replay
    print(f"records so far: "
          f"{int(np.sum(np.asarray(runner.executor.carry.record_counts)))}")

    print("killing the window operator's subtask 1...")
    runner.inject_failure([5])           # window vertex (id 1), subtask 1
    report = runner.recover()
    print(f"recovered: replayed {report.steps_replayed} supersteps / "
          f"{report.records_replayed} records in {report.recovery_ms:.0f} ms")

    runner.run_epoch()
    print("post-recovery epoch ran; metrics:")
    import json
    print(json.dumps(runner.metrics.snapshot(), indent=2, default=str))


if __name__ == "__main__":
    main()
