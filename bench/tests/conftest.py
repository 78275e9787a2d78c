import os
import sys
from pathlib import Path

os.environ.setdefault("JAX_PLATFORMS", "cpu")
BENCH = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(BENCH))
sys.path.insert(0, str(BENCH.parent / "src"))

import json  # noqa: E402

import pytest  # noqa: E402

TINY = Path(__file__).resolve().parent / "tiny"


@pytest.fixture(scope="session")
def tiny_cfg():
    return json.loads((TINY / "configs" / "tiny.json").read_text())


@pytest.fixture(scope="session")
def tiny_lib(tiny_cfg):
    """A small library and query pool of the test-only configuration."""
    import gen
    spec = gen.Spec.from_config(tiny_cfg)
    lib, table = gen.build_library(spec, 2**31 + 5)
    pool = gen.query_pool(spec, 2**31 + 5, 10, 64, table)
    return lib, pool, tiny_cfg
