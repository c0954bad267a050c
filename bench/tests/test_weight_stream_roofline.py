"""The weight-streaming kernel's share of the HBM roofline
(``bench/metrics/decode_weight_stream_roofline.py``): its byte count,
its kernel time from a trace, and its reading of recorded chip traces
with and without the kernel."""
from __future__ import annotations

import shutil
from pathlib import Path

import pytest

from bench import flops
from bench.trace_reduce import read_events, reduce_events
from bench.trace_scopes import innermost_scope, read_scoped_events

from conftest import TINY_QWEN

DATA = Path(__file__).parent / "data"
SPANS = ["gen_prompts", "prefill", "decode_step", "readback"]
NAME = "decode_weight_stream_roofline"


def _reader(bench_run):
    return bench_run.load_file(bench_run.BENCH / "metrics" / f"{NAME}.py")


def _run(tmp_path: Path, trace: Path, shapes: dict, batch: int) -> dict:
    """A traced run's reader input, its trace where ``bench/run.py``'s
    tracer leaves it beside ``bench/``."""
    where = tmp_path / ".bench_trace" / "plugins" / "profile" / "1"
    where.mkdir(parents=True)
    shutil.copy(trace, where / "host.xplane.pb")
    spans, chips = read_events(trace, "traced_window", SPANS)
    return {"found": {"bench": tmp_path / "bench",
                      "config": {"shapes": shapes}},
            "context": {"batch": batch}, "device_kind": "TPU v5 lite",
            "trace": reduce_events(spans, chips, "traced_window")}


def test_step_bytes_are_the_f32_matrices_and_the_bf16_rows(bench_run):
    reader = _reader(bench_run)
    cfg = dict(hidden_size=8, intermediate_size=16, num_hidden_layers=3,
               num_attention_heads=2, num_key_value_heads=1, head_dim=4,
               vocab_size=32)
    # a layer: q 8x8, k and v 8x4 each, o 8x8, gate, up and down 8x16
    assert flops.block_params(cfg) == 3 * (64 + 2 * 32 + 64 + 3 * 128)
    # rows read: 8 (q, k, v) + 8 (o) + 8 (gate, up) + 16 (down);
    # written: 16 (q, k, v) + 8 + 32 + 8
    rows = 3 * 5 * ((8 + 8 + 8 + 16) + (16 + 8 + 32 + 8))
    assert reader.step_bytes(cfg, 5) == 4 * flops.block_params(cfg) + 2 * rows


def test_kernel_seconds_counts_the_kernel_inside_the_program(bench_run):
    reader = _reader(bench_run)
    chip0 = {"modules": [("jit_serve_step(7)", 1.0, 5.0),
                         ("jit_other(8)", 6.0, 8.0)],
             "ops": [("weight_stream.1", "p/attn/weight_stream/pallas_call",
                      1.0, 2.0),
                     ("weight_stream", None, 2.0, 2.5),
                     ("weight_stream_x.3", None, 2.5, 3.0),   # not the kernel
                     ("fusion.4", None, 3.0, 4.0),
                     ("weight_stream.5", None, 6.0, 7.0),     # other program
                     ("weight_stream.6", None, 4.5, 5.5)]}    # outlives a run
    chip1 = {"modules": [("jit_serve_step(7)", 0.0, 5.0)],
             "ops": [("weight_stream.1", None, 0.0, 2.0)]}    # clipped at 0.5
    seconds = reader.kernel_seconds(
        {"/device:TPU:0": chip0, "/device:TPU:1": chip1}, (0.5, 10.0),
        "jit_serve_step", "weight_stream")
    assert seconds == pytest.approx((1.5 + 1.5) / 2)


def test_none_for_a_program_without_the_kernel(tmp_path, bench_run):
    """The parent's decode program, traced on one v5e: no op of the
    kernel, so nothing to read; nor where the run was not traced."""
    run = _run(tmp_path, DATA / "tiny_decode_scoped.xplane.pb", TINY_QWEN, 2)
    assert run["trace"]["programs"]["jit_serve_step"][1] > 0
    reader = _reader(bench_run)
    assert reader.read(run) is None
    assert reader.read({**run, "trace": None}) is None


def test_reads_a_recorded_chip_trace_with_the_kernel(tmp_path, bench_run):
    """The tiny decode cell recorded on one v5e (``TPU v5 lite``) with
    the weight-streaming decode program: prefill and 7 decode steps of
    2 layers, four kernel calls a layer, each under ``attn`` or
    ``mlp``."""
    trace = DATA / "tiny_decode_stream.xplane.pb"
    run = _run(tmp_path, trace, TINY_QWEN, 2)
    win, chips = read_scoped_events(trace, "traced_window")
    ops = [(n, p) for n, p, _, _ in chips["/device:TPU:0"]["ops"]
           if n.startswith("weight_stream.")]
    runs = run["trace"]["programs"]["jit_serve_step"][1]
    assert len(ops) == runs * 2 * 4
    assert {innermost_scope(p) for _, p in ops} == {"attn", "mlp"}
    reader = _reader(bench_run)
    seconds = reader.kernel_seconds(chips, win, "jit_serve_step",
                                    "weight_stream")
    want = 100.0 * reader.step_bytes(TINY_QWEN, 2) / (seconds / runs * 819e9)
    assert reader.read(run) == pytest.approx(want)
    assert 0 < want < 100
