"""User-facing job builder: the StreamExecutionEnvironment analog.

Capability parity with the reference's fluent DataStream API
(flink-streaming-java .../environment/StreamExecutionEnvironment.java:105,
datastream/DataStream.java & KeyedStream) pared to the batched-TPU operator
set. The builder accumulates vertices/edges into a :class:`JobGraph`;
``execute`` hands it to the runtime executor.

Example (the SocketWindowWordCount shape, README.md:46-77 of the reference):

    env = StreamEnvironment(num_key_groups=128)
    (env.source(SyntheticSource(vocab=1000, batch_size=64), parallelism=4)
        .key_by()
        .window_count(num_keys=1000, window_size=5)
        .sink())
    job = env.build()
"""

from __future__ import annotations

import dataclasses
from typing import Callable, Optional

from clonos_tpu.api.operators import (
    FilterOperator, HostFeedSource, IntervalJoinOperator, KeyedReduceOperator,
    MapOperator, Operator, OperatorStateCountOperator, SinkOperator,
    SyntheticSource, TumblingWindowCountOperator, UnionOperator,
)
from clonos_tpu.graph.job_graph import JobGraph, JobVertex, PartitionType


class DataStream:
    """Handle to a vertex's output; transformation methods append vertices."""

    def __init__(self, env: "StreamEnvironment", vertex: JobVertex,
                 keyed: bool = False):
        self._env = env
        self._vertex = vertex
        self._keyed = keyed

    # --- exchange selection --------------------------------------------------

    def key_by(self) -> "DataStream":
        """Marks the next operator's input as HASH-partitioned by key
        (KeyedStream analog). Keys are the record ``keys`` lane."""
        return DataStream(self._env, self._vertex, keyed=True)

    def _attach(self, name: str, op: Operator, parallelism: Optional[int],
                partition: Optional[PartitionType] = None,
                capacity: Optional[int] = None) -> "DataStream":
        p = parallelism or self._vertex.parallelism
        v = self._env.graph.add_vertex(name, op, p)
        if partition is None:
            if self._keyed:
                partition = PartitionType.HASH
            elif getattr(self, "_force_rebalance", False):
                partition = PartitionType.REBALANCE
            elif p == self._vertex.parallelism:
                partition = PartitionType.FORWARD
            else:
                partition = PartitionType.REBALANCE
        cap = capacity or self._env.default_edge_capacity
        self._env.graph.add_edge(self._vertex, v, partition, cap)
        return DataStream(self._env, v)

    # --- transformations -----------------------------------------------------

    def map(self, fn, name: str = "map", parallelism: Optional[int] = None,
            capacity: Optional[int] = None) -> "DataStream":
        return self._attach(name, MapOperator(fn), parallelism,
                            capacity=capacity)

    def filter(self, pred, name: str = "filter",
               parallelism: Optional[int] = None) -> "DataStream":
        return self._attach(name, FilterOperator(pred), parallelism)

    def count_through(self, name: str = "operator-state",
                      parallelism: Optional[int] = None) -> "DataStream":
        """Pass records on unchanged, counting them in per-subtask
        operator state (operators.OperatorStateCountOperator)."""
        return self._attach(name, OperatorStateCountOperator(), parallelism)

    def reduce(self, num_keys: int, reduce_fn=None, name: str = "reduce",
               parallelism: Optional[int] = None) -> "DataStream":
        import jax.numpy as jnp
        op = KeyedReduceOperator(num_keys=num_keys,
                                 reduce_fn=reduce_fn or jnp.add)
        if not self._keyed:
            raise ValueError("reduce requires key_by() first")
        return self._attach(name, op, parallelism)

    def window_count(self, num_keys: int, window_size: int,
                     name: str = "window",
                     parallelism: Optional[int] = None) -> "DataStream":
        if not self._keyed:
            raise ValueError("window_count requires key_by() first")
        return self._attach(
            name, TumblingWindowCountOperator(num_keys=num_keys,
                                              window_size=window_size),
            parallelism)

    def window_event_time(self, num_keys: int, window_size: int,
                          out_of_orderness: int = 0,
                          name: str = "event-window",
                          parallelism: Optional[int] = None
                          ) -> "DataStream":
        """Event-time tumbling window (watermark = pure fold over record
        timestamps with bounded out-of-orderness; see
        operators.EventTimeTumblingWindowOperator)."""
        from clonos_tpu.api.operators import EventTimeTumblingWindowOperator
        if not self._keyed:
            raise ValueError("window_event_time requires key_by() first")
        return self._attach(
            name, EventTimeTumblingWindowOperator(
                num_keys=num_keys, window_size=window_size,
                out_of_orderness=out_of_orderness), parallelism)

    def window_slide_event_time(self, num_keys: int, window_size: int,
                                slide: int, out_of_orderness: int = 0,
                                name: str = "sliding-window",
                                parallelism: Optional[int] = None
                                ) -> "DataStream":
        """Event-time sliding window (SlidingEventTimeWindows analog)."""
        from clonos_tpu.api.operators import SlidingEventTimeWindowOperator
        if not self._keyed:
            raise ValueError(
                "window_slide_event_time requires key_by() first")
        return self._attach(
            name, SlidingEventTimeWindowOperator(
                num_keys=num_keys, window_size=window_size, slide=slide,
                out_of_orderness=out_of_orderness), parallelism)

    def window_session(self, num_keys: int, gap: int,
                       out_of_orderness: int = 0,
                       capacity: Optional[int] = None,
                       own_columns: Optional[int] = None,
                       edge_capacity: Optional[int] = None,
                       name: str = "session-window",
                       parallelism: Optional[int] = None) -> "DataStream":
        """Event-time session windows per key, merging as Flink's
        ``EventTimeSessionWindows.withGap(gap)`` do: one row ``(key, sum,
        last timestamp + gap)`` a session, when the watermark reaches its
        end; two open sessions a key, merged when a record bridges them
        (operators.SessionWindowOperator; NEXmark query 11, "User
        Sessions", with values of 1). Requires key_by(), and ``gap >
        out_of_orderness``.

        ``capacity``: rows a subtask may emit a step (default: two a
        column, which drops none; rows past it are counted, and stop the
        run at the next fence, as a late record does). ``own_columns``
        and ``edge_capacity``: as :meth:`window_top` takes them."""
        from clonos_tpu.api.operators import SessionWindowOperator
        if not self._keyed:
            raise ValueError("window_session requires key_by() first")
        return self._attach(
            name, SessionWindowOperator(
                num_keys=num_keys, gap=gap,
                out_of_orderness=out_of_orderness, capacity=capacity,
                own_columns=own_columns), parallelism,
            capacity=edge_capacity)

    def _attach2(self, other: "DataStream", name: str, op: Operator,
                 parallelism: Optional[int],
                 capacity: Optional[int] = None) -> "DataStream":
        """Two-input attachment: edge order is (self=left, other=right)."""
        p = parallelism or self._vertex.parallelism
        v = self._env.graph.add_vertex(name, op, p)
        cap = capacity or self._env.default_edge_capacity
        for side in (self, other):
            if side._keyed:
                part = PartitionType.HASH
            elif getattr(side, "_force_rebalance", False):
                part = PartitionType.REBALANCE
            elif side._vertex.parallelism == p:
                part = PartitionType.FORWARD
            else:
                part = PartitionType.REBALANCE
            self._env.graph.add_edge(side._vertex, v, part, cap)
        return DataStream(self._env, v)

    def union(self, other: "DataStream", capacity: Optional[int] = None,
              name: str = "union",
              parallelism: Optional[int] = None) -> "DataStream":
        cap = capacity or self._env.default_edge_capacity
        return self._attach2(other, name, UnionOperator(capacity=cap),
                             parallelism, cap)

    def join(self, other: "DataStream", num_keys: int, window: int,
             interval: int, capacity: Optional[int] = None,
             name: str = "join",
             parallelism: Optional[int] = None) -> "DataStream":
        """Keyed interval join: self is the left (buffered) side, other the
        right (probing) side. Both inputs must be key_by()'d."""
        if not (self._keyed and other._keyed):
            raise ValueError("join requires key_by() on both inputs")
        cap = capacity or self._env.default_edge_capacity
        op = IntervalJoinOperator(num_keys=num_keys, window=window,
                                  interval=interval, capacity=cap)
        return self._attach2(other, name, op, parallelism, cap)

    def window_join(self, other: "DataStream", num_keys: int,
                    window_size: int, out_of_orderness: int = 0,
                    capacity: Optional[int] = None,
                    edge_capacity: Optional[int] = None,
                    name: str = "window-join",
                    parallelism: Optional[int] = None) -> "DataStream":
        """Tumbling event-time window join on equal key and equal window
        (``a.join(b).where(..).equalTo(..).window(
        TumblingEventTimeWindows.of(window_size))``): one row per (key,
        window) in which both inputs had a record, carrying the sum of
        ``other``'s values (operators.EventTimeWindowJoinOperator). Both
        inputs must be key_by()'d. ``capacity``: rows a subtask may emit
        a step; ``edge_capacity``: the receive window of BOTH input
        edges (a two-input vertex takes one).

        Both inputs are keyed, so a subtask only ever receives the keys
        it owns and the join's tables hold a column for those alone: the
        width is read off the plan — the most of the ``num_keys`` ids
        any of the vertex's subtasks owns under the environment's key
        groups, up to the next 128 lanes
        (``routing.own_columns_width``: 384 for 4,096 ids at parallelism
        16 and 128 groups) — and the planner binds the columns; where
        that is no narrower than ``num_keys`` the tables keep a column a
        key."""
        from clonos_tpu.api.operators import EventTimeWindowJoinOperator
        from clonos_tpu.parallel.routing import own_columns_width
        if not (self._keyed and other._keyed):
            raise ValueError("window_join requires key_by() on both inputs")
        op = EventTimeWindowJoinOperator(
            num_keys=num_keys, window_size=window_size,
            out_of_orderness=out_of_orderness,
            capacity=capacity or self._env.default_edge_capacity,
            own_columns=own_columns_width(
                num_keys, parallelism or self._vertex.parallelism,
                self._env.graph.num_key_groups))
        return self._attach2(other, name, op, parallelism, edge_capacity)

    def join_incremental(self, other: "DataStream", num_keys: int, ttl: int,
                         out_of_orderness: int = 0,
                         capacity: Optional[int] = None,
                         own_columns: Optional[int] = None,
                         bag_capacity: int = 1024,
                         edge_capacity: Optional[int] = None,
                         name: str = "incremental-join",
                         parallelism: Optional[int] = None) -> "DataStream":
        """Join over the whole history of two keyed streams, no window:
        ``self`` builds — a key's first record registers it, for ``ttl``
        of event time — and ``other`` probes: a record whose key is
        registered is a row ``(key, value, timestamp)`` at once, any
        other waits for its key, ``ttl`` at most (Beam's NEXmark
        ``Query3``, "Local Item Suggestion": persons and the auctions
        they sell; operators.IncrementalJoinOperator has the rule). Both
        inputs must be key_by()'d.

        ``capacity``: rows a subtask may emit a step; ``bag_capacity``:
        records a subtask may keep waiting; ``own_columns``: hold a
        column only for the keys a subtask owns (as :meth:`window_top`;
        required past the keys a dense table holds); ``edge_capacity``:
        the receive window of BOTH input edges. What passes a capacity
        is counted and stops the run at the next fence."""
        from clonos_tpu.api.operators import IncrementalJoinOperator
        if not (self._keyed and other._keyed):
            raise ValueError(
                "join_incremental requires key_by() on both inputs")
        op = IncrementalJoinOperator(
            num_keys=num_keys, ttl=ttl, out_of_orderness=out_of_orderness,
            capacity=capacity or self._env.default_edge_capacity,
            own_columns=own_columns, bag_capacity=bag_capacity)
        return self._attach2(other, name, op, parallelism, edge_capacity)

    def join_best_in_interval(self, other: "DataStream", num_keys: int,
                              length_of: Callable, floor_of: Callable,
                              emit_of: Callable, out_of_orderness: int = 0,
                              capacity: Optional[int] = None,
                              own_columns: Optional[int] = None,
                              pool_capacity: int = 1024,
                              edge_capacity: Optional[int] = None,
                              name: str = "best-in-interval",
                              parallelism: Optional[int] = None
                              ) -> "DataStream":
        """Join in which every key takes its interval from its own data
        and only an interval's best probe comes out: a record ``(key, v,
        t)`` of ``self`` opens ``[t, t + length_of(v))`` for its key
        with the floor ``floor_of(v)``; a record ``(key, price, t)`` of
        ``other`` counts for the interval of its key that holds ``t``
        if ``price >= floor``; when the watermark passes an interval's
        end it is one row ``(emit_of(v), largest price that counted,
        end - 1)``, or none (Beam's NEXmark ``WinningBids``: auctions and
        the bids inside their ``[dateTime, expires)`` at or over the
        reserve; operators.BestInIntervalJoinOperator has the rule). The
        three functions are traced elementwise over the value lane, as
        :meth:`map`'s ``fn`` is. Both inputs must be key_by()'d.

        ``capacity``: rows a subtask may emit a step; ``pool_capacity``:
        probes a subtask may keep waiting for the watermark to reach
        their own time; ``own_columns``: required — a column only for
        the keys a subtask owns (as :meth:`window_top`);
        ``edge_capacity``: the receive window of BOTH input edges. What
        passes a capacity is counted and stops the run at the next
        fence."""
        from clonos_tpu.api.operators import BestInIntervalJoinOperator
        if not (self._keyed and other._keyed):
            raise ValueError(
                "join_best_in_interval requires key_by() on both inputs")
        op = BestInIntervalJoinOperator(
            num_keys=num_keys, length_of=length_of, floor_of=floor_of,
            emit_of=emit_of, out_of_orderness=out_of_orderness,
            capacity=capacity or self._env.default_edge_capacity,
            own_columns=own_columns, pool_capacity=pool_capacity)
        return self._attach2(other, name, op, parallelism, edge_capacity)

    def window_mean(self, num_keys: int, window_size: int,
                    slide: Optional[int] = None, out_of_orderness: int = 0,
                    edge_capacity: Optional[int] = None,
                    name: str = "window-mean",
                    parallelism: Optional[int] = None) -> "DataStream":
        """Event-time windowed mean per key — sliding by ``slide``,
        tumbling without it — exact whatever the values add up to: one
        row ``(key, round-half-up(sum / count), window end - 1)`` per key
        a firing window holds a record of, values in ``[0, 2**30)``
        (operators.EventTimeWindowMeanOperator; behind
        :meth:`join_best_in_interval` it is NEXmark query 4, "Average
        Price for a Category"). Requires key_by(). A late record and a
        value out of range are counted and stop the run at the next
        fence. The rows lie on dense ``slot x key`` lanes: give the next
        edge ``open_windows x num_keys`` slots."""
        from clonos_tpu.api.operators import EventTimeWindowMeanOperator
        if not self._keyed:
            raise ValueError("window_mean requires key_by() first")
        op = EventTimeWindowMeanOperator(
            num_keys=num_keys, window_size=window_size,
            slide=slide or window_size, out_of_orderness=out_of_orderness)
        return self._attach(name, op, parallelism, capacity=edge_capacity)

    def window_top(self, num_keys: int, window_size: int,
                   slide: Optional[int] = None, out_of_orderness: int = 0,
                   capacity: Optional[int] = None,
                   own_columns: Optional[int] = None,
                   edge_capacity: Optional[int] = None,
                   name: str = "window-top",
                   parallelism: Optional[int] = None) -> "DataStream":
        """Event-time windowed sum per key — sliding by ``slide``,
        tumbling without it — of which a window that fires emits only
        its largest: one row ``(key, sum, window end - 1)`` per key whose
        sum equals the largest this subtask holds for the window, ties kept
        (operators.EventTimeWindowTopOperator). Two of them make NEXmark
        query 5, "Hot Items": behind ``key_by()`` a sliding count per
        item at the job's parallelism (values of 1 summed), whose rows —
        each subtask's own leaders — a second, tumbling by the first
        one's slide at ``parallelism=1``, sums per item and cuts to the
        leaders over all subtasks. Requires key_by().

        ``capacity``: rows a subtask may emit a step (rows past it are
        counted, and stop the run at the next fence). ``own_columns``:
        hold a table column only for the keys a subtask owns, that many
        a subtask; the planner binds them and refuses the job if a
        subtask owns more. Without it every subtask holds ``num_keys``
        columns. ``edge_capacity``: the receive window of the input
        edge — under skewed keys, the records the fullest subtask is
        sent in a step; the exchange counts what it drops, and a drop
        stops the run too."""
        from clonos_tpu.api.operators import EventTimeWindowTopOperator
        if not self._keyed:
            raise ValueError("window_top requires key_by() first")
        op = EventTimeWindowTopOperator(
            num_keys=num_keys, window_size=window_size,
            slide=slide or window_size, out_of_orderness=out_of_orderness,
            capacity=capacity or self._env.default_edge_capacity,
            own_columns=own_columns)
        return self._attach(name, op, parallelism, capacity=edge_capacity)

    def rebalance(self) -> "DataStream":
        s = DataStream(self._env, self._vertex)
        s._force_rebalance = True
        return s

    def sink(self, name: str = "sink",
             parallelism: Optional[int] = None,
             transactional: bool = False,
             capacity: Optional[int] = None) -> "DataStream":
        """``transactional=True`` routes emissions through the 2PC
        transaction log (exactly-once egress; runtime/txn.py)."""
        if transactional:
            from clonos_tpu.api.operators import TransactionalSinkOperator
            return self._attach(name, TransactionalSinkOperator(),
                                parallelism, capacity=capacity)
        return self._attach(name, SinkOperator(), parallelism,
                            capacity=capacity)

    @property
    def vertex(self) -> JobVertex:
        return self._vertex


class StreamEnvironment:
    """Builder root (StreamExecutionEnvironment analog)."""

    def __init__(self, name: str = "job", num_key_groups: int = 128,
                 sharing_depth: int = -1, default_edge_capacity: int = 256):
        self.graph = JobGraph(name=name, num_key_groups=num_key_groups,
                              sharing_depth=sharing_depth)
        self.default_edge_capacity = default_edge_capacity

    def source(self, op: Operator, parallelism: int = 1,
               name: str = "source") -> DataStream:
        v = self.graph.add_vertex(name, op, parallelism)
        return DataStream(self, v)

    def synthetic_source(self, vocab: int, batch_size: int,
                         parallelism: int = 1, name: str = "source",
                         rate_limit: Optional[int] = None) -> DataStream:
        return self.source(
            SyntheticSource(vocab=vocab, batch_size=batch_size,
                            rate_limit=rate_limit),
            parallelism, name)

    def host_source(self, batch_size: int, parallelism: int = 1,
                    name: str = "host-source") -> DataStream:
        """Externally-fed source (register a FeedReader on the executor:
        ``executor.register_feed(vertex_id, reader)``)."""
        return self.source(HostFeedSource(batch_size=batch_size),
                           parallelism, name)

    def build(self) -> JobGraph:
        self.graph.validate()
        return self.graph
