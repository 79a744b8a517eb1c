import os
import sys

REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
if REPO not in sys.path:
    sys.path.insert(0, REPO)

import pytest  # noqa: E402


@pytest.fixture
def root(tmp_path):
    """A benchmark root of tiny cells (`tiny_root.py`) in a temporary
    directory."""
    import tiny_root
    tiny_root.make(str(tmp_path))
    return str(tmp_path)
