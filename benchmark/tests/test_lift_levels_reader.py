"""The reader of the hoisted fold's skipped lifting levels, on recorded
layers and on the diagnostics of a small partition whose fold runs full
segments."""

import os

import pytest

from benchmark import harness

from conftest import BENCH

NAME = "lift_levels_skipped.batch"


def _reader():
    return harness.load_module(os.path.join(BENCH, "metrics", NAME + ".py"),
                               "reader_lift_levels_skipped_batch")


def _layer(*skipped):
    return {"partitions": [
        {"phase_times": {"build": 1.0},
         "diagnostics": {"fixpoint_rounds": 16.0,
                         **({} if s is None else {"lift_levels_skipped": s})}}
        for s in skipped], "window_compiles": 0, "trace": None}


@pytest.mark.parametrize("skipped,value", [
    ((44.0,), 44.0), ((44.0, 41.0, 46.0), 44.0), ((40.0, None, 42.0), 41.0)])
def test_reader_takes_the_median_per_partition(skipped, value):
    assert _reader().read(_layer(*skipped)) == pytest.approx(value)


@pytest.mark.parametrize("layer", [_layer(None), _layer(),
                                   {"partitions": [], "trace": None}])
def test_reader_finds_nothing_without_the_counter(layer):
    """The parent of the change that added the counter reads nothing,
    and does not raise."""
    assert _reader().read(layer) is None


def test_reader_on_a_partition_with_full_segments():
    """A scale-12 Graph500 graph through the tpu backend with the host
    tail held back, so the fold runs hoisted segments: the counter is
    in the diagnostics and the reader returns it."""
    from benchmark import graph500
    from sheep_tpu import get_backend
    from sheep_tpu.io.edgestream import EdgeStream

    cfg = {"scale": 12, "edge_factor": 16, "A": 0.57, "B": 0.19,
           "C": 0.19, "D": 0.05, "gen_seed": 20}
    g = graph500.Graph500(cfg, 2**31 + 11, True)
    res = get_backend("tpu", host_tail_threshold=1 << 10).partition(
        EdgeStream.from_array(g.base(), n_vertices=g.n), 8)
    d = res.diagnostics
    assert d["full_segments"] > 0
    assert d["lift_levels_live"] + d["lift_levels_skipped"] == \
        d["full_segments"] * (int(g.n).bit_length() - 1)
    got = _reader().read({"partitions": [{"phase_times": {},
                                          "diagnostics": d}]})
    assert got == d["lift_levels_skipped"]
