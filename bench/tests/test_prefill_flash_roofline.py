"""The prefill attention kernel's share of the bf16 peak
(``bench/metrics/prefill_flash_roofline.py``): its work, its kernel
time per prefill, and its reading of a recorded chip trace whose
prefill ran the XLA block scan."""
from __future__ import annotations

import shutil
from pathlib import Path

import pytest

from bench import flops
from bench.trace_reduce import read_events, reduce_events

from conftest import TINY_QWEN

DATA = Path(__file__).parent / "data"
SPANS = ["gen_prompts", "prefill", "decode_step", "readback"]
NAME = "prefill_flash_roofline"


def _reader(bench_run):
    return bench_run.load_file(bench_run.BENCH / "metrics" / f"{NAME}.py")


def test_work_is_the_causal_attention_term_of_prefill_flops(bench_run):
    reader = _reader(bench_run)
    b, s = 3, 40
    dense = b * (2.0 * flops.block_params(TINY_QWEN) * s
                 + 2.0 * flops.head_params(TINY_QWEN))
    assert reader.attention_work(TINY_QWEN, b, s) == pytest.approx(
        flops.prefill_flops(TINY_QWEN, b, s) - dense)
    # 2 layers, 4 query heads of 32: 4 * 2 * 4 * 32 FLOPs a key, and
    # 40 * 41 / 2 query-key pairs per prompt
    assert reader.attention_work(TINY_QWEN, b, s) == 3 * 1024 * 820


def test_reads_the_kernel_time_of_each_prefill(bench_run):
    """Two prefills in the window, the kernel's ops inside them counted,
    those of another program and those of other names not."""
    reader = _reader(bench_run)
    chip = {"modules": [("jit_prefill_step(3)", 0.0, 1.0),
                        ("jit_prefill_step(3)", 2.0, 3.0),
                        ("jit_serve_step(4)", 4.0, 5.0)],
            "ops": [("flash_prefill.7", "p/attn/flash_prefill", 0.1, 0.3),
                    ("flash_prefill.8", "p/attn/flash_prefill", 2.1, 2.2),
                    ("fusion.9", "p/attn/dot_general", 2.2, 2.9),
                    ("flash_prefill.7", None, 4.1, 4.2)]}
    run = {"found": {"bench": Path("bench"),
                     "config": {"shapes": TINY_QWEN}},
           "context": {"batch": 2, "prompt_len": 40},
           "device_kind": "TPU v5 lite",
           "trace": {"programs": {"jit_prefill_step": [2.0, 2.0]},
                     "window_s": 6.0},
           "scoped_events": ((0.0, 6.0), {"/device:TPU:0": chip})}
    per_prefill = (0.2 + 0.1) / 2
    want = 100.0 * reader.attention_work(TINY_QWEN, 2, 40) / (
        per_prefill * 197e12)
    assert reader.read(run) == pytest.approx(want)


def test_none_for_a_prefill_without_the_kernel(tmp_path, bench_run):
    """The tiny decode cell recorded on one v5e before the kernel: its
    prefill ran the block scan, so nothing to read; nor where the run
    was not traced."""
    trace = DATA / "tiny_decode_stream.xplane.pb"
    where = tmp_path / ".bench_trace" / "plugins" / "profile" / "1"
    where.mkdir(parents=True)
    shutil.copy(trace, where / "host.xplane.pb")
    spans, chips = read_events(trace, "traced_window", SPANS)
    run = {"found": {"bench": tmp_path / "bench",
                     "config": {"shapes": TINY_QWEN}},
           "context": {"batch": 2, "prompt_len": 32},
           "device_kind": "TPU v5 lite",
           "trace": reduce_events(spans, chips, "traced_window")}
    assert run["trace"]["programs"]["jit_prefill_step"][1] > 0
    reader = _reader(bench_run)
    assert reader.read(run) is None
    assert reader.read({**run, "trace": None}) is None
