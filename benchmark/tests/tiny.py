"""Tiny copies of the benchmark's cells, for the CPU tests: the same
configuration and traffic files with counts cut down, in a scratch checkout.
Only these tests set the size; the program has no knob for it.

The tiny size of each configuration and traffic mix is data, one overlay
file per name: benchmark/tests/tiny_sizes/configs/<config>.json and
benchmark/tests/tiny_sizes/traffic/<mix>.json. An overlay is laid on the
full file by one rule (`overlay`), so a later configuration or mix brings
its tiny size as a new file and no code here changes."""

from __future__ import annotations

import json
import os
import shutil

REPO = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
SIZES = os.path.join("benchmark", "tests", "tiny_sizes")


def _load(path):
    with open(path) as fh:
        return json.load(fh)


def _dump(obj, path):
    os.makedirs(os.path.dirname(path), exist_ok=True)
    with open(path, "w") as fh:
        json.dump(obj, fh, indent=1)


def overlay(base, over):
    """`over` laid on `base`: a dict merges key by key, a list of dicts
    element by element by index (elements past the overlay's end stay),
    and anything else, a scalar or a list of scalars, replaces."""
    if isinstance(base, dict) and isinstance(over, dict):
        return {**base, **{k: overlay(base.get(k), v) for k, v in over.items()}}
    if (isinstance(base, list) and isinstance(over, list) and over
            and all(isinstance(x, dict) for x in over)):
        if len(over) > len(base):
            raise ValueError(f"an overlay of {len(over)} elements on a list of {len(base)}")
        return [overlay(b, o) for b, o in zip(base, over)] + base[len(over):]
    return over


def shrunk(src: str, part: str, name: str, full: str) -> dict:
    """The file `full` (relative to `src`) under its tiny overlay,
    `SIZES/<part>/<name>.json`; a missing overlay is an error that names it."""
    rel = os.path.join(SIZES, part, f"{name}.json")
    if not os.path.exists(os.path.join(src, rel)):
        raise FileNotFoundError(
            f"{part} {name!r} has no tiny size for the CPU tests: add {rel}, "
            f"an overlay on {full} that cuts its counts")
    return overlay(_load(os.path.join(src, full)), _load(os.path.join(src, rel)))


def make_root(tmp: str, src: str = REPO) -> str:
    """A scratch checkout holding `src`'s BENCHMARK.json and benchmark/, with
    every configuration and traffic file that a cell uses shrunk; returns
    its path."""
    root = os.path.join(tmp, "checkout")
    shutil.copytree(os.path.join(src, "benchmark"), os.path.join(root, "benchmark"),
                    ignore=shutil.ignore_patterns("__pycache__", "tests"))
    bench = _load(os.path.join(src, "BENCHMARK.json"))
    files = {c["name"]: c["file"] for c in bench["configs"]}
    for name in sorted({w["config"] for w in bench["workloads"]}):
        _dump(shrunk(src, "configs", name, files[name]), os.path.join(root, files[name]))
    for name in sorted({w["traffic"] for w in bench["workloads"]}):
        full = os.path.join("benchmark", "traffic", f"{name}.json")
        _dump(shrunk(src, "traffic", name, full), os.path.join(root, full))
    _dump(bench, os.path.join(root, "BENCHMARK.json"))
    return root
