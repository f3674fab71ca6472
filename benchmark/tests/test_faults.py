"""``correct`` must come out false when the timed path is broken, and
for the control (the reference with a stated guarantee broken).

Faults, planted under a whole run of a tiny cell on the CPU (the look
for a chip is lifted; everything else is the run as on the chip):

- a step that returns its state unchanged (the fixpoint step);
- half of the batch left out (half the edges reach the partition);
- an answer altered where it is produced (the scorer's cut).

The exchange between chips does not exist in these one-chip cells.
"""

import json

import pytest

from benchmark import control

from test_harness import _run


def _unchanged_step(monkeypatch):
    from sheep_tpu.ops import elim

    monkeypatch.setattr(elim, "build_chunk_step_adaptive_pos",
                        lambda P, *a, **kw: (P, 0))


def _half_batch(monkeypatch):
    from sheep_tpu.io.edgestream import EdgeStream

    monkeypatch.setattr(EdgeStream, "from_array", classmethod(
        lambda cls, edges, n_vertices=None: cls(
            edges=edges[: len(edges) // 2], n_vertices=n_vertices)))


def _altered_cut(monkeypatch):
    from sheep_tpu.ops import score

    orig = score.score_chunk
    monkeypatch.setattr(score, "score_chunk",
                        lambda *a: (lambda c, t: (c + 1, t))(*orig(*a)))


@pytest.mark.parametrize("fault", [_unchanged_step, _half_batch,
                                   _altered_cut])
def test_batch_fault_is_not_correct(tiny_root, monkeypatch, fault):
    rc, clean = _run(tiny_root, "tiny-batch.batch")
    assert rc == 0 and clean["correct"]
    fault(monkeypatch)
    rc, line = _run(tiny_root, "tiny-batch.batch")
    assert rc == 0 and line["correct"] is False


def test_control_is_not_correct(tiny_root, capsys):
    assert control.main(["--workload", "tiny-batch.batch", "--seeds",
                         "1,2,3"], root=tiny_root) == 0
    rows = [json.loads(x) for x in capsys.readouterr().out.splitlines()]
    assert len(rows) == 3 and not any(r["correct"] for r in rows)
    # every compared number reads above its limit under the control
    assert all(c["value"] > c["limit"] for r in rows
               for c in r["checks"].values())
    assert [r["seed"] for r in rows] == [1, 2, 3]
