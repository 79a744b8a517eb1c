import os
import sys

REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
if REPO not in sys.path:
    sys.path.insert(0, REPO)

import pytest  # noqa: E402

import tiny_root  # noqa: E402  (beside this file: pytest prepends its directory)


@pytest.fixture
def root(tmp_path):
    """A benchmark root of tiny cells (`tiny_root.py`) in a temporary
    directory."""
    tiny_root.make(str(tmp_path))
    return str(tmp_path)


@pytest.fixture(scope="session", params=tiny_root.STANDINGS)
def standing(request):
    """Every test of the real `BENCHMARK.json` runs twice: over the file
    as it stands, and over the file after the next PR's arrival (a
    configuration, a cell that joins a shared per-layer entry's
    `workloads`, two per-layer entries of its own at the END:
    `tiny_root.ARRIVAL`). A test that pins the list's tail, its length or
    its set of names fails here, in the PR that writes it."""
    return request.param


@pytest.fixture(scope="session")
def spec(standing):
    return tiny_root.spec_of(standing)


@pytest.fixture(scope="session")
def spec_root(standing, tmp_path_factory):
    """The root whose `benchmark/` holds the data files beside `spec`."""
    if standing == "as_it_stands":
        return tiny_root.REPO
    return tiny_root.arrival_root(tmp_path_factory.mktemp("arrival") / "root")
