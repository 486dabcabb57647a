"""The data and check path's own cost, measured without the program."""
import json

from bench import scale

from .conftest import DATA, ROOT


def test_scale_runs_every_stage_and_finds_nothing_wrong(capsys):
    assert scale.main(["--config", str(DATA / "graph-1kg.json"),
                       "--reference-length", "100000", "--seed",
                       str(2 ** 31 + 1), "--reads", "512", "--sample",
                       "32"]) == 0
    rows = {r["stage"]: r for r in map(json.loads,
                                       capsys.readouterr().out.splitlines())}
    assert list(rows) == ["make_data", "read_source", "reads", "check",
                          "total"]
    assert rows["reads"]["reads"] == 512
    assert rows["check"]["answers"] == 33
    assert rows["check"]["bad_alignments"] == 0
    assert rows["check"]["excess_max"] >= 0  # edits made, not the least
    assert all(r["seconds"] >= 0 and r["peak_rss_mb"] > 0
               for r in rows.values())


def test_scale_builds_the_whole_graph_on_request(capsys):
    assert scale.main(["--config", str(ROOT / "bench/configs/"
                                       "linear-chr1.json"),
                       "--reference-length", "50000", "--seed", "3"]) == 0
    assert scale.main(["--config", str(DATA / "graph-10m.json"),
                       "--reference-length", "50000", "--seed", "3",
                       "--whole-graph"]) == 0
    rows = [json.loads(x) for x in capsys.readouterr().out.splitlines()]
    assert [r["stage"] for r in rows] == ["make_data", "read_source",
                                          "reads", "make_data",
                                          "whole_graph"]
    assert rows[-1]["nodes"] > 50_000
