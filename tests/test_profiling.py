"""Device-time profiling (utils/profiling.py): the jax.profiler capture
wrapper, the spans it shows on the host timeline, and `simon profile`."""

import jax
import jax.numpy as jnp

from benchmark import trace as trace_mod
from open_simulator_tpu.utils import tracing
from open_simulator_tpu.utils.profiling import capture_device_trace


def test_capture_device_trace_writes_into_out_dir(tmp_path):
    out = tmp_path / "devtrace"
    rep = capture_device_trace(
        str(out), fn=lambda: jnp.sum(jnp.ones(32)).block_until_ready()
    )
    assert rep["ok"] is True, rep
    assert rep["trace_dir"] == str(out)
    assert rep["seconds"] >= 0
    assert out.is_dir()


def test_capture_device_trace_failure_degrades_not_raises(tmp_path):
    def boom():
        raise RuntimeError("profiled workload exploded")

    rep = capture_device_trace(str(tmp_path / "t2"), fn=boom)
    assert rep["ok"] is False
    assert "profiled workload exploded" in rep["error"]


def test_span_is_a_profiler_annotation_without_meta(tmp_path):
    """Inside a capture, each span is a host event of exactly its name (no
    `#k=v#` meta folded in), nested on the calling thread's line — the
    line the benchmark labels idle device gaps from."""
    out = str(tmp_path / "annot")
    jax.profiler.start_trace(out)
    try:
        with tracing.span("probe-outer", pods=3):
            with tracing.span("probe-inner", site="x"):
                tracing.fetch(jnp.arange(4) * 2, "probe")
    finally:
        jax.profiler.stop_trace()
    lines = [
        ln for pl in trace_mod.load(out) if pl["name"].startswith("/host")
        for ln in pl["lines"]
        if any(ev[0] == "probe-outer" for ev in ln["events"])
    ]
    assert len(lines) == 1
    ev = {name: (s, e) for name, s, e in lines[0]["events"]
          if name in ("probe-outer", "probe-inner", "host-sync")}
    assert set(ev) == {"probe-outer", "probe-inner", "host-sync"}
    assert ev["probe-outer"][0] <= ev["probe-inner"][0]
    assert ev["probe-inner"][1] <= ev["probe-outer"][1]
    assert ev["probe-inner"][0] <= ev["host-sync"][0]
    assert not any("#" in ev[0] for ln in lines for ev in ln["events"])


def test_profile_needs_a_command(capsys):
    from open_simulator_tpu.cli import main as cli

    assert cli.main(["profile"]) == 2
    assert "needs a command" in capsys.readouterr().err
