"""``correct``: the window's answers against the plain reference.

Each number named under ``limits`` in ``bench/checks/<cell>.json`` is
compared with its limit, and the run is correct when none exceeds it;
the others are reported beside them, for information:

* ``unanswered``: reads offered in the window with no answer a minute
  after the close, or an error for an answer;
* ``window_compiles``: executor traces between the window's open and the
  last answer (every shape was to be warmed up before);
* ``misplaced``: answered reads placed further than the tolerance from
  where they were drawn (seed and filter);
* ``unmapped_pct``: answered reads reported unmapped, in percent of the
  answered (seed and filter recall);
* ``bad_alignments``: sampled answers whose CIGAR does not spell the read
  against the reference at the reported place with the reported edits,
  or, on a graph, whose path leaves the graph's edges or disagrees with
  the reported backbone position (align and the kernels);
* ``excess_max``: the most that a sampled answer's distance exceeds the
  exact anchored optimum at the same place (align and the kernels).

The sample is drawn from the seed among the mapped answers, and always
holds the longest read answered.
"""
from __future__ import annotations

import numpy as np

from . import reference as ref_mod
from .drive import Window
from .traffic import Reads


def compare(win: Window, reads: Reads, data, answer, spec: dict,
            compiles: int, rng: np.random.Generator) -> tuple[dict, dict]:
    """``({name: (value, limit)}, {name: value})``: the numbers compared
    and those only reported; see the module docstring."""
    limits = spec["limits"]
    n = len(win.results)
    got = [i for i in range(n) if win.results[i] is not None]
    ans = {i: answer(win.results[i]) for i in got}
    pos = np.array([ans[i][0] for i in got], np.int64)
    true = reads.true_pos[got]
    mapped = pos >= 0
    out = {
        "unanswered": n - len(got),
        "window_compiles": int(compiles),
        "misplaced": int(np.sum(mapped & (np.abs(pos - true)
                                          > spec["position_tolerance"]))),
        "unmapped_pct": (100.0 * float(np.mean(~mapped)) if got else 100.0),
    }
    idx = [i for i, m in zip(got, mapped) if m]
    if idx:
        k = min(spec["sample"], len(idx))
        pick = set(rng.choice(len(idx), size=k, replace=False).tolist())
        pick.add(int(np.argmax([len(reads.read(i)) for i in idx])))
        sample = [idx[j] for j in sorted(pick)]
    else:
        sample = []
    bad, excess = _alignments(sample, ans, reads, data)
    out["bad_alignments"] = bad
    out["excess_max"] = excess
    return ({name: (float(v), float(limits[name]))
             for name, v in out.items() if name in limits},
            {name: float(v) for name, v in out.items() if name not in limits})


def _alignments(sample, ans, reads: Reads, data) -> tuple[int, int]:
    """``(bad, excess_max)`` over the sampled answers.  On a graph, each
    answer is checked against the nodes around it alone (``answer_graph``),
    under the whole graph's node ids."""
    if not sample:
        return 0, 0
    v = data.variants
    c = ref_mod.coords(len(data.reference), v) if v is not None else None
    bad = 0
    ok_rows, texts = [], []
    for i in sample:
        position, distance, ops, path = ans[i]
        read = reads.read(i)
        span = len(read) + max(distance, 0) + 8
        if v is None:
            err = ref_mod.cigar_error(
                ops, read, data.reference[position:position + span],
                distance)
            bases, succ, start = data.reference, None, position
        else:
            span *= 2  # alt nodes interleave the backbone
            g, start = ref_mod.answer_graph(data.reference, v, c, path, span)
            err = ref_mod.path_error(g, ops, path, read, distance, position)
            bases, succ, start = g.bases, g.succ, start - g.first
        if err is not None:
            bad += 1
            continue
        ok_rows.append(i)
        texts.append(ref_mod.windows(bases, succ, np.array([start]),
                                     np.array([span])))
    if not ok_rows:
        return bad, 0
    b, s = ref_mod.stack_windows(texts)
    opt = ref_mod.anchored_distance([reads.read(i) for i in ok_rows], b, s)
    dist = np.array([ans[i][1] for i in ok_rows])
    bad += int(np.sum(dist < opt))  # below the optimum: the reference is off
    return bad, int(np.max(dist - opt))


def report_lines(checks: dict, info: dict | None = None) -> list[str]:
    """One plain line per number: its name, value and limit."""
    return ([f"info {name}: {v:g} (not compared)"
             for name, v in (info or {}).items()]
            + [f"check {name}: {v:g} (limit {lim:g})"
               for name, (v, lim) in checks.items()])


def is_correct(checks: dict) -> bool:
    return all(v <= lim for v, lim in checks.values())
