"""The reader of the duplicate constraints the warm segments collapse,
on recorded layers and on the diagnostics of a small partition."""

import os

import pytest

from benchmark import harness

from conftest import BENCH

NAME = "warm_duplicates.batch"


def _reader():
    return harness.load_module(os.path.join(BENCH, "metrics", NAME + ".py"),
                               "reader_warm_duplicates_batch")


def _layer(*dups):
    return {"partitions": [
        {"phase_times": {"build": 1.0},
         "diagnostics": {"fixpoint_rounds": 10.0,
                         **({} if d is None else {"warm_duplicates": d})}}
        for d in dups], "window_compiles": 0, "trace": None}


@pytest.mark.parametrize("dups,value", [
    ((2597221.0,), 2597221.0), ((2597221.0, 2590000.0, 2601000.0), 2597221.0),
    ((2590000.0, None, 2600000.0), 2595000.0)])
def test_reader_takes_the_median_per_partition(dups, value):
    assert _reader().read(_layer(*dups)) == pytest.approx(value)


@pytest.mark.parametrize("layer", [_layer(None), _layer(),
                                   {"partitions": [], "trace": None}])
def test_reader_finds_nothing_without_the_counter(layer):
    """The parent of the change that added the counter reads nothing,
    and does not raise."""
    assert _reader().read(layer) is None


def test_reader_on_a_partition_whose_warm_segments_collapse():
    """A scale-12 Graph500 graph through the tpu backend in chunks wide
    enough for warm segments, with a host-tail threshold under the
    first chunk's live count: the counter is in the diagnostics, counts
    some duplicates, and the reader returns it."""
    from benchmark import graph500
    from sheep_tpu import get_backend
    from sheep_tpu.io.edgestream import EdgeStream

    cfg = {"scale": 12, "edge_factor": 16, "A": 0.57, "B": 0.19,
           "C": 0.19, "D": 0.05, "gen_seed": 20}
    g = graph500.Graph500(cfg, 2**31 + 11, True)
    res = get_backend("tpu", chunk_edges=1 << 15,
                      host_tail_threshold=1 << 12).partition(
        EdgeStream.from_array(g.base(), n_vertices=g.n), 8)
    d = res.diagnostics
    assert d["warm_segments"] > 0 and d["warm_duplicates"] > 0
    got = _reader().read({"partitions": [{"phase_times": {},
                                          "diagnostics": d}]})
    assert got == d["warm_duplicates"]
