"""A whole run on the CPU, sound and with the timed path broken underneath.

Each cell's run is driven end to end at a tiny size (``tiny_root``),
skipping only the look for a chip.  Sound, it comes out correct; with
the control's settings, with half of every flush's answers left out, or
with an answer altered where the executor produces it, ``correct`` comes
out false, and the number that catches it is named.
"""
import numpy as np
import pytest

from bench.harness import cell

CELLS = ("linear-sr-batch", "graph-sr-batch", "linear-sr-online")


def run(root, workload, **kw):
    return cell.run_cell(root, workload, 2 ** 31 + 11, 0.5, False,
                         t_process=0.0, platform="cpu", settle_s=10.0, **kw)


def failing(result):
    return {k for k, v in result["checks"].items() if v["value"] > v["limit"]}


def drop_half(engine):
    deliver = engine._deliver

    def half(cap, reqs, epoch, lens, res, stats):
        deliver(cap, reqs[:(len(reqs) + 1) // 2], epoch, lens, res, stats)
    engine._deliver = half


class _Altered:
    def __init__(self, fn):
        self.fn = fn

    def __getattr__(self, name):
        return getattr(self.fn, name)

    def __call__(self, *args):
        res = self.fn(*args)
        d = np.asarray(res.distance)
        return res._replace(distance=np.where(d >= 0, d + 1, d))


def alter_answers(engine):
    make = engine._executor
    engine._executor = lambda *a, **k: _Altered(make(*a, **k))


@pytest.mark.parametrize("workload", CELLS)
def test_sound_run_is_correct(tiny_root, workload):
    r = run(tiny_root, workload)
    assert r["correct"], r["checks"]
    assert r["attempted"] > 0 and r["failed"] == 0
    assert r["device"]["platform"] == "cpu"
    assert list(r)[-1] == "checks"


@pytest.mark.parametrize("workload", CELLS)
def test_control_is_not_correct(tiny_root, workload):
    r = run(tiny_root, workload, control=True)
    assert not r["correct"]
    assert "unmapped_pct" in failing(r)


@pytest.mark.parametrize("workload", CELLS)
def test_half_the_batch_left_out_is_not_correct(tiny_root, workload):
    r = run(tiny_root, workload, fault=drop_half)
    assert not r["correct"]
    assert "unanswered" in failing(r)


@pytest.mark.parametrize("workload", CELLS)
def test_an_altered_answer_is_not_correct(tiny_root, workload):
    r = run(tiny_root, workload, fault=alter_answers)
    assert not r["correct"]
    assert "bad_alignments" in failing(r)
