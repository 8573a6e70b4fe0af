"""Native host-runtime parity: the compiled quantity parser and row hasher
(open_simulator_tpu/native/osim_native.cpp) must agree with the exact Python
implementations on every value they accept.

The reference's host layer is compiled Go; this module is the TPU build's
equivalent compiled layer (SURVEY §2.4). All tests skip when no compiler is
available — the Python fallbacks carry full behavior.
"""

import math
import random

import numpy as np
import pytest

from open_simulator_tpu import native
from open_simulator_tpu.utils.quantity import parse_quantity

pytestmark = pytest.mark.skipif(
    not native.available(), reason="native library unavailable (no compiler)"
)


def exact_quad(s):
    q = parse_quantity(s)
    m, b = q * 1000, q
    return (
        int(math.ceil(m)),
        int(math.floor(m)),
        int(math.ceil(b)),
        int(math.floor(b)),
    )


CORPUS = [
    "0", "1", "250m", "1500m", "2", "512Mi", "4Gi", "1Ki", "3Ti", "2Pi",
    "107374182400", "1.5Ti", "100k", "2M", "3G", "4T", "5P",
    "0.1", "  3  ", "+2.5Gi", "-1500m", "-2", "1e3", "2E-2", "1e0", "5e6",
    "3n", "7u", ".5", "5.", "0.000001", "999999999", "12.345Mi", "1.000000001",
]

INVALID = ["", "bogus", "1.2.3", "Ki", "--1", "1..", "e3", "1ee3", "1 Gi", "1KiB"]


def test_scalar_parity_with_exact_python():
    for s in CORPUS:
        got = native.parse_quantity_one(s)
        if got is None:
            continue  # punting to the exact path is always legal
        assert got == exact_quad(s), s


def test_invalid_values_rejected():
    for s in INVALID:
        assert native.parse_quantity_one(s) is None


def test_large_negative_exponent_punts_not_wraps():
    # 10^40 would wrap u128; the parser must punt (None) so the exact
    # Fraction path answers, never return a silently-wrapped value.
    s = "3" + "0" * 35 + "e-40"
    got = native.parse_quantity_one(s)
    assert got is None or got == exact_quad(s)
    from open_simulator_tpu.utils.quantity import parse_quad

    parse_quad.cache_clear()
    assert parse_quad(s) == exact_quad(s) == (1, 0, 1, 0)


def test_randomized_parity():
    rng = random.Random(0)
    suffixes = ["", "m", "k", "M", "G", "Ki", "Mi", "Gi", "Ti", "n", "u"]
    for _ in range(2000):
        num = rng.choice(
            [
                str(rng.randint(0, 10**12)),
                f"{rng.randint(0, 10**6)}.{rng.randint(0, 999999)}",
                f".{rng.randint(1, 999)}",
            ]
        )
        s = ("-" if rng.random() < 0.2 else "") + num + rng.choice(suffixes)
        got = native.parse_quantity_one(s)
        if got is not None:
            assert got == exact_quad(s), s


def test_acceptance_matches_python_grammar():
    # Whatever Python accepts, native must either match or punt — and
    # whatever Python REJECTS, native must reject too.
    for s in CORPUS + INVALID:
        try:
            parse_quantity(s)
            py_ok = True
        except ValueError:
            py_ok = False
        got = native.parse_quantity_one(s)
        if not py_ok:
            assert got is None, s


def test_hash_rows_identity_and_difference():
    rng = np.random.default_rng(0)
    rows = rng.integers(0, 255, (1000, 137), dtype=np.uint8)
    h = native.hash_rows(rows)
    assert h.shape == (1000, 2)
    # identical rows hash identically
    rows2 = rows.copy()
    rows2[5] = rows2[4]
    h2 = native.hash_rows(rows2)
    assert (h2[4] == h2[5]).all()
    # single-byte flips change the hash
    rows3 = rows.copy()
    rows3[7, 100] ^= 1
    h3 = native.hash_rows(rows3)
    assert (h3[7] != h[7]).any()
    # no collisions across 1000 random distinct rows
    assert len(np.unique(h.view([("a", np.uint64), ("b", np.uint64)]))) == 1000


def test_group_runs_use_native_hashing():
    # end-to-end: grouped scheduling still detects identical-pod runs
    from open_simulator_tpu.core.objects import Pod
    from open_simulator_tpu.ops.encode import Encoder, encode_pods
    from open_simulator_tpu.ops.grouped import group_runs

    def pod(name, cpu):
        return Pod.from_dict(
            {
                "metadata": {"name": name, "namespace": "d"},
                "spec": {
                    "containers": [
                        {"name": "c", "resources": {"requests": {"cpu": cpu}}}
                    ]
                },
            }
        )

    pods = [pod(f"a{i}", "1") for i in range(5)] + [pod(f"b{i}", "2") for i in range(3)]
    enc = Encoder()
    enc.register_pods(pods)
    batch = encode_pods(enc, pods)
    assert group_runs(batch) == [(0, 5), (5, 3)]


def test_library_name_tracks_source(tmp_path, monkeypatch):
    """A library built from another version of the source is never
    loaded: its file name carries the source's hash."""
    src = tmp_path / "osim_native.cpp"
    with open(native._SRC, "rb") as fh:
        src.write_bytes(fh.read())
    monkeypatch.setattr(native, "_SRC", str(src))
    before = native._so_path()
    src.write_bytes(src.read_bytes() + b"\n// edited\n")
    assert native._so_path() != before
