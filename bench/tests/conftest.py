"""Tests of the benchmark's own code, on the CPU: ``python -m pytest
bench/tests`` from the repository root."""
import json
import os
import shutil
import sys
import tempfile
from pathlib import Path

import pytest

BENCH = Path(__file__).resolve().parents[1]
ROOT = BENCH.parent
DATA = Path(__file__).resolve().parent / "data"
for p in (str(BENCH), str(ROOT / "src")):
    if p not in sys.path:
        sys.path.insert(0, p)
os.environ.setdefault("JAX_COMPILATION_CACHE_DIR",
                      tempfile.mkdtemp(prefix="bench-tests-cache-"))


def make_root(tmp: Path) -> Path:
    """A checkout-shaped directory holding the benchmark's files plus the
    test configuration ``tiny`` and its mixes ``tiny-closed`` and
    ``tiny-open``, listed as cells of the same names."""
    shutil.copytree(BENCH, tmp / "bench",
                    ignore=shutil.ignore_patterns(".jax_cache", "tests",
                                                  "__pycache__"))
    shutil.copy(DATA / "tiny-config.json", tmp / "bench/configs/tiny.json")
    for mix in ("tiny-closed", "tiny-open"):
        shutil.copy(DATA / f"{mix}.json", tmp / f"bench/traffic/{mix}.json")
    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    bench["workloads"] += [
        {"name": mix, "config": "tiny", "traffic": mix, "chips": 1,
         "why": "test"} for mix in ("tiny-closed", "tiny-open")]
    for m in bench["end_to_end"] + bench["per_layer"]:
        if "workloads" in m:
            m["workloads"] += ["tiny-closed", "tiny-open"]
    (tmp / "BENCHMARK.json").write_text(json.dumps(bench))
    return tmp


@pytest.fixture(scope="session")
def tiny_root(tmp_path_factory) -> Path:
    return make_root(tmp_path_factory.mktemp("checkout"))


TEST_PEAKS = {"bf16_flops": 1e12, "hbm_bytes_per_s": 1e11}
