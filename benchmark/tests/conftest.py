import os

import pytest

pytest.register_assert_rewrite("benchmark.tests.helpers")

from benchmark.tests import tiny  # noqa: E402

# files that a later PR would add (test_cells.py lays them on a copy),
# among them a test_kind_<kind>.py that runs only there
collect_ignore = ["fixtures"]


@pytest.fixture(scope="session")
def tiny_root(tmp_path_factory):
    """A scratch checkout with every cell cut to a CPU size."""
    return tiny.make_root(str(tmp_path_factory.mktemp("bench")))


@pytest.fixture(autouse=True, scope="session")
def _compile_cache(tmp_path_factory):
    os.environ.setdefault("JAX_COMPILATION_CACHE_DIR",
                          str(tmp_path_factory.mktemp("jax_cache")))
