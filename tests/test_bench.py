"""Bench harness contract tests: segment routing, loud segment failure, the
one-process-per-chip guard and the chunk knob.

A segment that fails or times out fails the run with a non-zero exit and
names itself; nothing is re-run on another platform.
"""

import json
import os
import subprocess
import sys

import pytest

import bench


def test_headline_segment_routes_to_headline(monkeypatch, capsys, tmp_path):
    # _segment_main enables the persistent compilation cache; keep it out
    # of the checkout
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path / "xla"))
    seen = {}

    def fake_headline(pods, nodes):
        seen["sizes"] = (pods, nodes)
        return {"ok": True}

    monkeypatch.setattr(bench, "_run_headline", fake_headline)
    rc = bench._segment_main("headline", 2_000, 200)
    assert rc == 0
    assert seen["sizes"] == (2_000, 200)
    out = capsys.readouterr().out.strip().splitlines()[-1]
    parsed = json.loads(out)
    assert parsed["ok"] is True
    # every segment's JSON carries its process's metrics snapshot
    assert isinstance(parsed["metrics"], dict)


def _drive_main(monkeypatch, capsys, segment_results, argv=None):
    """Run bench.main() with canned per-segment results.

    segment_results: {segment_name: dict} — what _run_segment returns.
    Returns (rc, calls, parsed stdout JSON, stderr); each recorded call is
    (name, pods, nodes).
    """
    calls = []

    def fake_run_segment(name, pods, nodes):
        calls.append((name, pods, nodes))
        return dict(segment_results[name])

    monkeypatch.setattr(bench, "_run_segment", fake_run_segment)
    monkeypatch.setenv("JAX_PLATFORMS", "tpu")
    monkeypatch.setattr(
        sys, "argv", argv or ["bench.py", "--configs", "none"]
    )
    rc = bench.main()
    cap = capsys.readouterr()
    out = json.loads(cap.out.strip().splitlines()[-1])
    return rc, calls, out, cap.err


def test_passing_headline_exits_zero(monkeypatch, capsys):
    rc, calls, out, _ = _drive_main(
        monkeypatch, capsys, {"headline": {"value": 3.0, "device": "TPU_0"}},
    )
    assert rc == 0
    assert [c[0] for c in calls] == ["headline"]
    assert out["requested_platform"] == "tpu"
    assert "failed_segments" not in out


def test_failed_headline_fails_the_run_loudly(monkeypatch, capsys):
    """A headline that times out is reported, never re-run elsewhere, and
    the run exits non-zero naming the segment and the platform."""
    rc, calls, out, err = _drive_main(
        monkeypatch, capsys,
        {"headline": {"error": "timeout after 1200s (device hang?)"}},
    )
    assert rc == 1
    assert [c[0] for c in calls] == ["headline"]  # exactly once
    assert out["failed_segments"] == ["headline"]
    assert "segment(s) failed on tpu: headline" in err


def test_failed_config_fails_the_run_after_the_rest(monkeypatch, capsys):
    rc, calls, out, err = _drive_main(
        monkeypatch, capsys,
        {
            "headline": {"value": 3.0, "device": "TPU_0"},
            "fit_1k_100n": {"error": "rc=1: boom"},
            "gpushare_5k": {"value": 1.0, "device": "TPU_0"},
        },
        argv=["bench.py", "--configs", "fit_1k_100n,gpushare_5k"],
    )
    assert rc == 1
    assert [c[0] for c in calls] == ["headline", "fit_1k_100n", "gpushare_5k"]
    assert out["failed_segments"] == ["fit_1k_100n"]
    assert out["configs"]["gpushare_5k"]["value"] == 1.0
    assert "fit_1k_100n" in err


def test_segment_refused_once_parent_imported_jax():
    """A parent that has touched JAX holds the chip; the child that needs
    it would fail or hang, so the segment runner refuses up front."""
    import jax  # noqa: F401 - the point of the test

    with pytest.raises(RuntimeError, match="imported jax"):
        bench._run_segment("headline", 10, 10)


def test_segment_timeout_is_an_error(monkeypatch):
    monkeypatch.delitem(sys.modules, "jax")

    def hang(*a, **k):
        raise subprocess.TimeoutExpired(cmd="bench", timeout=1)

    monkeypatch.setattr(bench.subprocess, "run", hang)
    res = bench._run_segment("fit_1k_100n", 10, 10)
    assert res["error"].startswith("timeout after")


def test_bad_chunk_fails_fast_not_hangs(tmp_path):
    """chunk<=0 would spin the fast-path chunk loop forever; it must exit
    immediately with the knob's name in the message (both malformed and
    non-positive values)."""
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    env["JAX_COMPILATION_CACHE_DIR"] = str(tmp_path / "xla")
    for bad in ("0", "4k"):
        env["OSIM_HEADLINE_CHUNK"] = bad
        r = subprocess.run(
            [sys.executable, bench.__file__, "--quick", "--configs", "none"],
            env=env, capture_output=True, text=True, timeout=120,
        )
        assert r.returncode != 0
        assert "OSIM_HEADLINE_CHUNK" in (r.stderr + r.stdout)
