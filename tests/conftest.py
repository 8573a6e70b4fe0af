"""Test config: JAX onto a virtual 8-device CPU mesh.

The tests run on the CPU (``JAX_PLATFORMS=cpu``); sharding correctness is
validated on 8 virtual CPU devices. The pin happens here, before any test
module imports jax. On-chip checks live in ``chip_smoke.py``; the chip's
compiler is exercised without a chip by ``tests/test_chip_compile.py``.
"""

import os

os.environ["JAX_PLATFORMS"] = "cpu"
flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in flags:
    os.environ["XLA_FLAGS"] = (
        flags + " --xla_force_host_platform_device_count=8"
    ).strip()
os.environ.setdefault("JAX_ENABLE_X64", "0")

import jax  # noqa: E402

# a plugin may have imported jax before this file: make the pin stick
jax.config.update("jax_platforms", "cpu")


# ---------------------------------------------------------------------------
# Shared stub scheduler-extender endpoint (used by test_extenders.py and
# test_parallel.py) — one copy of the extender wire protocol to keep in sync.
# ---------------------------------------------------------------------------

import json  # noqa: E402
import threading  # noqa: E402
from http.server import BaseHTTPRequestHandler, HTTPServer  # noqa: E402

import pytest  # noqa: E402


class _StubExtender:
    """In-process extender endpoint. `behavior` is a dict:
    - allow: set of node names the filter keeps (None = keep all)
    - failed: {node: msg} map returned as FailedNodes
    - scores: {node: int 0..10} returned by prioritize
    - error: string returned as ExtenderFilterResult.Error
    - http_error: int -> respond with that status code
    - http_error_body: bytes sent as the http_error response body
    - fail_first: int -> respond 503 to the first N requests, then behave
      normally (flaky-then-recovers, for retry tests)
    - preempt_allow: set of node names kept in ProcessPreemption (None =
      keep all); victims echo back unchanged (as MetaVictims UIDs)
    - preempt_raw: full NodeNameToMetaVictims dict to return verbatim
      (overrides preempt_allow)
    Records every request body in .calls and every request's headers (keys
    lowercased) in .request_headers, index-aligned with .calls."""

    def __init__(self, behavior):
        self.behavior = behavior
        self.calls = []
        self.request_headers = []
        stub = self

        class Handler(BaseHTTPRequestHandler):
            def log_message(self, *a):
                pass

            def do_POST(self):
                length = int(self.headers.get("Content-Length", 0))
                body = json.loads(self.rfile.read(length) or b"{}")
                stub.calls.append((self.path, body))
                stub.request_headers.append(
                    {k.lower(): v for k, v in self.headers.items()}
                )
                fail_first = stub.behavior.get("fail_first", 0)
                if fail_first and len(stub.calls) <= fail_first:
                    self.send_response(503)
                    self.end_headers()
                    return
                if stub.behavior.get("http_error"):
                    err_body = stub.behavior.get("http_error_body") or b""
                    self.send_response(stub.behavior["http_error"])
                    self.send_header("Content-Length", str(len(err_body)))
                    self.end_headers()
                    if err_body:
                        self.wfile.write(err_body)
                    return
                if self.path.endswith("/filter"):
                    names = body.get("NodeNames")
                    if names is None:
                        names = [
                            (i.get("metadata") or {}).get("name")
                            for i in (body.get("Nodes") or {}).get("items") or []
                        ]
                    allow = stub.behavior.get("allow")
                    failed = stub.behavior.get("failed") or {}
                    keep = [
                        n for n in names
                        if (allow is None or n in allow) and n not in failed
                    ]
                    if body.get("NodeNames") is not None:
                        resp = {
                            "NodeNames": keep,
                            "FailedNodes": failed,
                            "Error": stub.behavior.get("error", ""),
                        }
                    else:
                        resp = {
                            "Nodes": {
                                "items": [
                                    {"metadata": {"name": n}} for n in keep
                                ]
                            },
                            "FailedNodes": failed,
                            "Error": stub.behavior.get("error", ""),
                        }
                elif self.path.endswith("/preempt"):
                    if stub.behavior.get("preempt_raw") is not None:
                        resp = {
                            "NodeNameToMetaVictims": stub.behavior["preempt_raw"]
                        }
                    else:
                        # echo victims back as MetaVictims, keeping only
                        # preempt_allow nodes (None = keep all)
                        allow = stub.behavior.get("preempt_allow")
                        meta = body.get("NodeNameToMetaVictims")
                        if meta is None:
                            meta = {
                                node: {
                                    "Pods": [
                                        {
                                            "UID": (
                                                (p.get("metadata") or {}).get("uid")
                                                or "{}/{}".format(
                                                    (p.get("metadata") or {}).get(
                                                        "namespace", "default"
                                                    ),
                                                    (p.get("metadata") or {}).get(
                                                        "name", ""
                                                    ),
                                                )
                                            )
                                        }
                                        for p in (v or {}).get("Pods") or []
                                    ],
                                    "NumPDBViolations": (v or {}).get(
                                        "NumPDBViolations", 0
                                    ),
                                }
                                for node, v in (
                                    body.get("NodeNameToVictims") or {}
                                ).items()
                            }
                        resp = {
                            "NodeNameToMetaVictims": {
                                node: v
                                for node, v in meta.items()
                                if allow is None or node in allow
                            }
                        }
                else:  # prioritize
                    names = body.get("NodeNames")
                    if names is None:
                        names = [
                            (i.get("metadata") or {}).get("name")
                            for i in (body.get("Nodes") or {}).get("items") or []
                        ]
                    scores = stub.behavior.get("scores") or {}
                    resp = [
                        {"Host": n, "Score": int(scores.get(n, 0))}
                        for n in names
                    ]
                out = json.dumps(resp).encode()
                self.send_response(200)
                self.send_header("Content-Type", "application/json")
                self.send_header("Content-Length", str(len(out)))
                self.end_headers()
                self.wfile.write(out)

        self.server = HTTPServer(("127.0.0.1", 0), Handler)
        self.thread = threading.Thread(
            target=self.server.serve_forever, daemon=True
        )
        self.thread.start()
        self.url = f"http://127.0.0.1:{self.server.server_address[1]}/ext"

    def close(self):
        self.server.shutdown()
        self.server.server_close()


@pytest.fixture(autouse=True, scope="session")
def _flight_dumps_to_tmp(tmp_path_factory):
    """Watchdog fires and crash hooks inside tests dump flight-recorder
    artifacts; without a configured dir those land in the repo CWD. Point
    them at a session tmp dir (tests that assert on dumps override it)."""
    if not os.environ.get("OSIM_FLIGHT_DIR", "").strip():
        os.environ["OSIM_FLIGHT_DIR"] = str(
            tmp_path_factory.mktemp("flightrec")
        )
    yield


@pytest.fixture(autouse=True)
def _reset_resilience():
    """Breakers live in a process-wide endpoint-keyed registry and fault
    plans install globally; clear both around every test so one test's
    tripped breaker or leaked plan can't leak into the next."""
    from open_simulator_tpu.resilience import faults
    from open_simulator_tpu.resilience.policy import reset_breakers

    reset_breakers()
    faults.uninstall_plan()
    yield
    reset_breakers()
    faults.uninstall_plan()


@pytest.fixture
def stub_factory():
    stubs = []

    def make(behavior):
        s = _StubExtender(behavior)
        stubs.append(s)
        return s

    yield make
    for s in stubs:
        s.close()
