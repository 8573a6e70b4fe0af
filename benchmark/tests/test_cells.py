"""CPU rehearsal of every cell through the one command, at the tiny sizes of
benchmark/tests/tiny_sizes/; cells added as files only; no chip."""

import filecmp
import glob
import json
import os
import shutil
import subprocess
import sys

import pytest

from benchmark import run
from benchmark.tests.helpers import (
    add_files, cells, kinds_without_tests, rehearse, rehearse_traced)
from benchmark.tests.tiny import REPO, make_root

SEED = 3_000_000_019
CLOSED_LOOP = cells(REPO, kind="closed_loop_simulate")
#: what later PRs add, each case a tree of new files and BENCHMARK.json entries
FILES_ONLY = os.path.join(os.path.dirname(__file__), "fixtures", "files_only")


@pytest.mark.parametrize("cell", cells(REPO))
def test_cell_rehearsal(tiny_root, cell, capsys):
    res = rehearse(tiny_root, cell, SEED)
    line = capsys.readouterr().out.strip().splitlines()[-1]
    assert list(json.loads(line))[-1] == "checks"
    if cell in CLOSED_LOOP:
        assert set(res["metrics"]) == {"setup_s", "pods_per_s"}


@pytest.mark.parametrize("cell", cells(REPO))
def test_cell_traced_rehearsal(tiny_root, cell):
    res = rehearse_traced(tiny_root, cell, SEED + 1)
    if cell in CLOSED_LOOP:  # a CPU run never reports the device metrics
        assert {"schedule_ms", "encode_ms", "window_compiles"} <= set(res["metrics"])
        assert "device_idle_share" not in res["metrics"]


def test_every_kind_has_its_test_file():
    """A traffic kind joins with benchmark/tests/test_kind_<kind>.py, which
    shows `correct` coming out false under planted faults."""
    assert kinds_without_tests(REPO) == []


def test_same_seed_same_inputs(tiny_root):
    from benchmark import generator

    spec = generator.find_cell(tiny_root, "envelope_5k.mixed")
    a = generator.build_cluster(spec["config"], SEED)
    b = generator.build_cluster(spec["config"], SEED)
    c = generator.build_cluster(spec["config"], SEED + 7)
    assert (a.bound_node == b.bound_node).all()
    assert (a.bound_node != c.bound_node).any()
    import numpy as np

    per_node = lambda cl: sorted(np.bincount(cl.bound_node, minlength=len(cl.nodes)))
    assert per_node(a) == per_node(c)  # the same work, placed anew


def _copy_of_repo(dst):
    shutil.copytree(os.path.join(REPO, "benchmark"), os.path.join(dst, "benchmark"),
                    ignore=shutil.ignore_patterns("__pycache__"))
    shutil.copy(os.path.join(REPO, "BENCHMARK.json"), dst)


def _changed(a, b):
    """Files of the tree `a` that the tree `b` lacks or holds other bytes of."""
    cmp = filecmp.dircmp(a, b, ignore=["__pycache__"])
    return [os.path.join(a, f) for f in cmp.left_only + cmp.diff_files] + [
        f for sub in cmp.common_dirs for f in _changed(os.path.join(a, sub), os.path.join(b, sub))]


@pytest.mark.parametrize("case", ("new_config", "new_kind"))
def test_cell_from_files_only(case, tmp_path, monkeypatch):
    """A later PR adds its cell as new files and BENCHMARK.json entries only:
    `new_config` a configuration in the generator's schema on the kind
    `closed_loop_simulate`, with its mix and a per-layer reader;
    `new_kind` a configuration that the generator cannot read, its mix, the
    kind's driver, its own reference, a per-layer reader and the kind's
    test file. Both bring their tiny overlays. `make_root` shrinks the copy,
    its cells pass the rehearsals that hold for any cell, and the kind's
    own test file passes in the copy."""
    import benchmark.reference

    repo = str(tmp_path / "repo")
    _copy_of_repo(repo)
    new = add_files(os.path.join(FILES_ONLY, case), repo)
    assert _changed(os.path.join(REPO, "benchmark"), os.path.join(repo, "benchmark")) == []
    assert kinds_without_tests(repo) == []

    root = make_root(str(tmp_path), src=repo)
    # in process, `benchmark.reference` is this repository's package: let it
    # also find the checkout's new modules, as the checkout's own package would
    monkeypatch.setattr(benchmark.reference, "__path__",
                        [*benchmark.reference.__path__, f"{root}/benchmark/reference"])
    for cell in new:
        rehearse(root, cell, SEED)
        rehearse_traced(root, cell, SEED + 1)

    # the case's own test files run in the copy, where pytest finds them
    added = os.path.join(FILES_ONLY, case)
    tests = [os.path.relpath(t, added) for t in glob.glob(f"{added}/benchmark/tests/test_*.py")]
    if tests:
        env = dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH=REPO)  # the program
        p = subprocess.run([sys.executable, "-m", "pytest", "-q", "-p", "no:cacheprovider",
                            *tests], cwd=repo, env=env, capture_output=True, text=True,
                           timeout=600)
        # a kind whose cells the copy did not find would skip, not fail
        assert p.returncode == 0 and "skipped" not in p.stdout, p.stdout[-4000:]


def test_no_accelerator_no_result(tiny_root, capsys):
    with pytest.raises(SystemExit) as e:
        run.main(["--workload", "envelope_5k.mixed", "--seed", "1", "--seconds", "1"],
                 root=tiny_root)
    assert e.value.code != 0
    assert capsys.readouterr().out == ""


def test_without_the_program_no_result(tmp_path):
    """A directory holding only BENCHMARK.json and benchmark/ exits non-zero
    and prints no result."""
    _copy_of_repo(tmp_path)
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run([sys.executable, "benchmark/run.py", "--workload",
                        "envelope_5k.mixed", "--seed", "1", "--seconds", "1",
                        "--trace", "0"], cwd=tmp_path, env=env,
                       capture_output=True, text=True, timeout=300)
    assert p.returncode != 0
    assert p.stdout.strip() == ""
