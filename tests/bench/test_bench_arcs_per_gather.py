"""``arcs_per_gather``: arcs per element the in-core sparse gather reads,
from ``schedule_stats["sparse_gather"]`` of the last timed trial; nothing
to read where a plan reports no such block."""
import dataclasses

import pytest

from bench import harness

CELLS = ("kron22.pr.incore", "urand22.pr.stream")


@pytest.fixture
def reader():
    return harness.load_module(harness.ROOT, "metrics", "arcs_per_gather")


def _run(*stats):
    cell = harness.load_cell(harness.ROOT, CELLS[0])
    return harness.Run(cell=cell, n=64, m=1000, trials=[
        harness.Trial(float(i), float(i + 1), 2, s)
        for i, s in enumerate(stats)])


def test_listed_for_the_in_core_cell_only():
    assert "arcs_per_gather" in harness.load_cell(
        harness.ROOT, CELLS[0]).trace_metrics
    assert "arcs_per_gather" not in harness.load_cell(
        harness.ROOT, CELLS[1]).trace_metrics


@pytest.mark.parametrize("gathered, engaged, want", [
    (80, True, 12.5), (1000, False, 1.0)])
def test_reads_the_last_trial(reader, gathered, engaged, want):
    first = dict(sparse_gather=dict(arcs=1000, gathered=1, engaged=True))
    last = dict(sparse_gather=dict(arcs=1000, gathered=gathered,
                                   engaged=engaged))
    assert reader.read(_run(first, last)) == want


def test_nothing_to_read_without_the_block(reader):
    # the streamed plan, and an in-core plan that predates the counter
    assert reader.read(_run({})) is None
    assert reader.read(dataclasses.replace(_run({}), trials=[])) is None
