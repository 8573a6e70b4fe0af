import json
import os
import shutil

from benchmark import run


def _load(path):
    with open(path) as fh:
        return json.load(fh)


def run_cell(root, cell, seed, *extra, seconds=1, trace=0):
    """One run of the command, in process, on the CPU; -> result dict."""
    argv = ["--workload", cell, "--seed", str(seed), "--seconds", str(seconds),
            "--trace", str(trace), *extra]
    return run.main(argv, root=root, allow_cpu=True)


def _kind(root, traffic):
    """The `kind` of a traffic mix, which names its driver."""
    return _load(f"{root}/benchmark/traffic/{traffic}.json")["kind"]


def cells(root, kind=None):
    """The cells of BENCHMARK.json, or those whose traffic has `kind`."""
    return [w["name"] for w in _load(f"{root}/BENCHMARK.json")["workloads"]
            if kind is None or _kind(root, w["traffic"]) == kind]


def kinds_without_tests(root):
    """Traffic kinds used by a cell that have no benchmark/tests/test_kind_<kind>.py."""
    kinds = {_kind(root, w["traffic"]) for w in _load(f"{root}/BENCHMARK.json")["workloads"]}
    return sorted(k for k in kinds
                  if not os.path.exists(f"{root}/benchmark/tests/test_kind_{k}.py"))


def metrics_of(root, cell, section, source=None):
    """Names of the `section` metrics of BENCHMARK.json that apply to `cell`."""
    return {m["name"] for m in _load(f"{root}/BENCHMARK.json")[section]
            if run._applies(m, cell) and source in (None, m["source"])}


def rehearse(root, cell, seed):
    """An untraced run, held to what holds for any cell."""
    res = run_cell(root, cell, seed)
    assert res["correct"], res["checks"]
    assert res["failed"] == 0 and res["attempted"] >= 1
    assert set(res["metrics"]) == metrics_of(root, cell, "end_to_end")
    assert all(m["value"] > 0 for m in res["metrics"].values()), res["metrics"]
    return res


def rehearse_traced(root, cell, seed):
    """A traced run, held to what holds for any cell: on the CPU there is no
    device plane, so no metric read from the device trace."""
    res = run_cell(root, cell, seed, trace=1)
    assert res["correct"], res["checks"]
    assert res["failed"] == 0 and res["attempted"] >= 1
    got = set(res["metrics"])
    assert got and got <= metrics_of(root, cell, "per_layer")
    assert not got & metrics_of(root, cell, "per_layer", source="device_trace")
    return res


def add_files(case, repo):
    """Lay what a later PR adds, kept in the directory `case`, onto the copy
    `repo` of the repository: the files under `case`/benchmark/, each new
    (an existing path is an error), and the entries of
    `case`/BENCHMARK.add.json appended to BENCHMARK.json's lists, with
    `add_to_workloads` naming existing metrics that list the new cells."""
    for dirpath, dirs, files in os.walk(os.path.join(case, "benchmark")):
        dirs[:] = [d for d in dirs if d != "__pycache__"]
        for f in files:
            rel = os.path.relpath(os.path.join(dirpath, f), case)
            dst = os.path.join(repo, rel)
            if os.path.exists(dst):
                raise FileExistsError(f"{rel} is in the repository already")
            os.makedirs(os.path.dirname(dst), exist_ok=True)
            shutil.copy(os.path.join(dirpath, f), dst)
    add = _load(os.path.join(case, "BENCHMARK.add.json"))
    bench = _load(os.path.join(repo, "BENCHMARK.json"))
    for key in ("configs", "workloads", "end_to_end", "per_layer"):
        bench[key] += add.get(key, [])
    metrics = {m["name"]: m for m in bench["end_to_end"] + bench["per_layer"]}
    for name, new in add.get("add_to_workloads", {}).items():
        metrics[name]["workloads"] += new
    with open(os.path.join(repo, "BENCHMARK.json"), "w") as fh:
        json.dump(bench, fh, indent=1)
    return [w["name"] for w in add.get("workloads", [])]
