"""The tiny sizes: one overlay per configuration and traffic mix under
benchmark/tests/tiny_sizes/, laid on the full file by one rule."""

import json
import os
import shutil

import pytest

from benchmark.tests.tiny import REPO, make_root, overlay

#: the shrunk files, as (path in the file) -> value on the full file
PINNED = {
    "benchmark/configs/plan_100k_10k.json": {  # one node rung; no bound pods
        ("node_groups", 0, "count"): 60, ("candidate", "max"): 4},
    "benchmark/configs/envelope_5k.json": {
        ("node_groups", 0, "count"): 50, ("bound", "count"): 1000},
    "benchmark/traffic/fit.json": {  # candidates: none, too few, enough
        ("deployments", 0, "replicas"): 300, ("deployments", 1, "replicas"): 300,
        ("candidate_counts",): [0, 1, 4]},
    "benchmark/traffic/mixed.json": {
        **{("deployments", i, "replicas"): (5, 20)[i % 2] for i in range(16)},
        ("candidate_counts",): [0]},
}


@pytest.mark.parametrize("rel", sorted(PINNED))
def test_shrunk_file_pinned(tiny_root, rel):
    with open(os.path.join(REPO, rel)) as fh:
        want = json.load(fh)
    for path, value in PINNED[rel].items():
        *up, last = path
        node = want
        for key in up:
            node = node[key]
        node[last] = value
    with open(os.path.join(tiny_root, rel)) as fh:
        assert fh.read() == json.dumps(want, indent=1)


def test_list_of_dicts_merges_by_index():
    base = {"groups": [{"count": 10, "cpu": "4"}, {"count": 20, "cpu": "8"}, {"count": 30}]}
    got = overlay(base, {"groups": [{"count": 1}, {"cpu": "2"}]})
    assert got == {"groups": [{"count": 1, "cpu": "4"}, {"count": 20, "cpu": "2"},
                              {"count": 30}]}
    assert base["groups"][0]["count"] == 10  # the full file's data is not touched


def test_scalar_list_replaces():
    base = {"candidate_counts": [0, 144, 288], "checks": {"missed": 0}, "n": 5}
    got = overlay(base, {"candidate_counts": [0], "n": 1})
    assert got == {"candidate_counts": [0], "checks": {"missed": 0}, "n": 1}


@pytest.mark.parametrize("part", ("configs", "traffic"))
def test_missing_overlay_names_the_file(tmp_path, part):
    """A cell whose configuration or mix has no tiny size stops make_root
    with the path to add; nothing runs at full size on the CPU."""
    repo = tmp_path / "repo"
    shutil.copytree(os.path.join(REPO, "benchmark"), repo / "benchmark",
                    ignore=shutil.ignore_patterns("__pycache__"))
    with open(os.path.join(REPO, "BENCHMARK.json")) as fh:
        bench = json.load(fh)
    config, traffic = "envelope_5k", "mixed"
    if part == "configs":
        config = "envelope_copy"
        bench["configs"].append(dict(bench["configs"][1], name=config))
    else:
        traffic = "mixed_copy"
        shutil.copy(repo / "benchmark/traffic/mixed.json", repo / f"benchmark/traffic/{traffic}.json")
    bench["workloads"].append({"name": "copy.cell", "config": config, "traffic": traffic,
                               "chips": 1, "why": "test"})
    (repo / "BENCHMARK.json").write_text(json.dumps(bench))
    name = config if part == "configs" else traffic
    with pytest.raises(FileNotFoundError,
                       match=f"add benchmark/tests/tiny_sizes/{part}/{name}.json"):
        make_root(str(tmp_path), src=str(repo))
