"""The per-layer readers of the program's own counters (native core,
transfer, chunk wait, compiles) on recorded layers, and on a whole traced
run of a tiny cell."""

import os
import time

import pytest

from benchmark import harness, run

from conftest import BENCH

READERS = ("native_s.batch", "transfer_s.batch", "transfer_max_s.batch",
           "chunk_wait_s.batch", "compiles.batch")


def _reader(name):
    return harness.load_module(os.path.join(BENCH, "metrics", name + ".py"),
                               "reader_" + name.replace(".", "_"))


def _part(native, transfer, tmax, wait, compiles):
    return {"phase_times": {"build": 1.0},
            "diagnostics": {"fixpoint_rounds": 3.0, "t_native_s": native,
                            "t_transfer_s": transfer,
                            "t_transfer_max_s": tmax,
                            "t_chunk_wait_s": wait,
                            "compiles": compiles}}


LAYER = {"partitions": [_part(0.5, 0.10, 0.01, 0.2, 0.0),
                        _part(0.7, 0.30, 2.50, 0.1, 0.0),
                        _part(0.6, 0.20, 0.02, 0.3, 1.0)],
         "window_compiles": 1, "trace": None}


@pytest.mark.parametrize("name,value", [
    ("native_s.batch", 0.6), ("transfer_s.batch", 0.2),
    ("transfer_max_s.batch", 2.5), ("chunk_wait_s.batch", 0.2),
    ("compiles.batch", 1.0)])
def test_reader_on_a_recorded_layer(name, value):
    assert _reader(name).read(LAYER) == pytest.approx(value)


@pytest.mark.parametrize("name", READERS)
def test_reader_finds_nothing_without_its_key(name):
    """A program that lacks the counter (the parent of the change that
    added it) reads nothing, and does not raise."""
    bare = {"partitions": [{"phase_times": {"build": 1.0},
                            "diagnostics": {"fixpoint_rounds": 3.0}}],
            "window_compiles": 0, "trace": None}
    assert _reader(name).read(bare) is None
    assert _reader(name).read({"partitions": [], "trace": None}) is None


def test_traced_tiny_cell_reports_every_reader(tiny_root):
    import contextlib
    import io
    import json

    out = io.StringIO()
    with contextlib.redirect_stdout(out):
        rc = run.main(["--workload", "tiny-batch.batch", "--seed",
                       str(2**31 + 11), "--seconds", "1", "--trace", "1"],
                      root=tiny_root, require_tpu=False,
                      t0=time.perf_counter())
    assert rc == 0
    line = json.loads(out.getvalue().strip().splitlines()[-1])
    assert line["correct"] is True
    metrics = line["metrics"]
    assert set(READERS) <= set(metrics)
    assert all(metrics[m]["value"] is not None for m in READERS)
    assert metrics["compiles.batch"]["value"] == \
        metrics["window_compiles.batch"]["value"]
