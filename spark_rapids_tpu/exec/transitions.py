"""Row <-> columnar transition execs.

Reference analog: GpuRowToColumnarExec (GpuRowToColumnarExec.scala:37),
GpuColumnarToRowExec (GpuColumnarToRowExec.scala:38), GpuBringBackToHost.
The planner inserts these at every CPU/TPU boundary; the transition
optimizer's job (GpuTransitionOverrides.scala:38) of fusing adjacent
transitions is done here by construction — the overrides pass only ever
creates one transition per boundary.
"""
from __future__ import annotations

from typing import Iterator, List

from ..columnar import ColumnarBatch
from ..columnar.batch import batch_from_rows
from ..conf import MAX_READER_BATCH_SIZE_ROWS, RapidsConf
from ..cpu.plan import CpuExec
from ..types import StructType
from .base import Metric, TpuExec, section_metric, timed


class RowToColumnarExec(TpuExec):
    """CPU rows -> device batches (host build + single upload per batch)."""

    def __init__(self, conf: RapidsConf, cpu_child: CpuExec):
        super().__init__(conf)
        self.cpu_child = cpu_child
        self._batch_rows = conf.get(MAX_READER_BATCH_SIZE_ROWS)

    @property
    def output_schema(self) -> StructType:
        return self.cpu_child.output_schema

    @property
    def num_partitions(self) -> int:
        return self.cpu_child.num_partitions

    def describe(self):
        return "RowToColumnarExec"

    def tree_string(self, indent: int = 0) -> str:
        lines = ["  " * indent + self.describe()]
        lines.append(self.cpu_child.tree_string(indent + 1))
        return "\n".join(lines)

    def execute_partition(self, index: int) -> Iterator[ColumnarBatch]:
        buf: List[tuple] = []
        for row in self.cpu_child.execute_rows_partition(index):
            buf.append(row)
            if len(buf) >= self._batch_rows:
                yield self.record_batch(batch_from_rows(buf, self.output_schema))
                buf = []
        if buf:
            yield self.record_batch(batch_from_rows(buf, self.output_schema))


class ColumnarToRowExec(CpuExec):
    """Device batches -> host rows (the collect boundary)."""

    def __init__(self, conf: RapidsConf, tpu_child: TpuExec):
        from ..conf import ENABLE_TRACE

        super().__init__(conf)
        self.tpu_child = tpu_child
        self._trace = conf.get(ENABLE_TRACE)
        self.metrics: dict = {}

    def section(self, name: str, **counts):
        """TpuExec.section's shape without becoming a TpuExec (this node
        emits rows, not batches): the same ``timed()``, so the boundary's
        sections are metrics always and spans under sql.trace.enabled,
        and ``exec.base.phase`` sections below it (the batch's ``d2h``)
        time into this node."""
        metric_name = section_metric(name)
        metric = self.metrics.get(metric_name)
        if metric is None:
            metric = self.metrics[metric_name] = Metric(metric_name)
        return timed(metric, f"{self.node_name}.{name}", self._trace,
                     owner=self, **counts)

    @property
    def output_schema(self) -> StructType:
        return self.tpu_child.output_schema

    @property
    def num_partitions(self) -> int:
        return self.tpu_child.num_partitions

    def describe(self):
        return "ColumnarToRowExec"

    def tree_string(self, indent: int = 0) -> str:
        lines = ["  " * indent + self.describe()]
        lines.append(self.tpu_child.tree_string(indent + 1))
        return "\n".join(lines)

    def execute_rows_partition(self, index: int) -> Iterator[tuple]:
        for batch in self.tpu_child.execute_partition(index):
            with self.section("to_rows"):
                rows = batch.to_rows()
            yield from rows
