"""Tiny cells for the benchmark's CPU tests: the committed cells' files,
found by name as a run finds them, with sizes cut to what a test run
holds."""
from __future__ import annotations

import importlib.util
import sys
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[2]
for p in (ROOT, ROOT / "src"):
    if str(p) not in sys.path:
        sys.path.insert(0, str(p))

TINY_QWEN = dict(hidden_size=128, intermediate_size=256, num_hidden_layers=2,
                 num_attention_heads=4, num_key_value_heads=2, head_dim=32,
                 vocab_size=512)
TINY_SERVE = dict(batch=2, prompt_len=32, gen_len=8)


def load_run():
    spec = importlib.util.spec_from_file_location("bench_run_under_test",
                                                  ROOT / "bench" / "run.py")
    mod = importlib.util.module_from_spec(spec)
    sys.modules[spec.name] = mod
    spec.loader.exec_module(mod)
    return mod


@pytest.fixture(scope="session")
def bench_run():
    return load_run()


@pytest.fixture
def tiny(bench_run):
    """``tiny(cell)``: the cell as ``find_cell`` finds it, cut to tiny
    sizes (the gen length of a one-token mix stays 1)."""
    def make(cell: str) -> dict:
        found = bench_run.find_cell(cell)
        found["config"]["shapes"].update(TINY_QWEN)
        gen = min(found["traffic"]["gen_len"], TINY_SERVE["gen_len"])
        found["traffic"].update(TINY_SERVE, gen_len=gen)
        found["limits"]["sample_requests"] = 2
        return found
    return make
