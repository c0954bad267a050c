"""The benchmark's arithmetic: trace reduction, model FLOPs, the peak
table and the statistics the end-to-end metrics take."""
from __future__ import annotations

import json
from pathlib import Path

import pytest

from bench import flops, peaks, stats
from bench.trace_reduce import (idle_stretches, reduce_events,
                                union_length)

DATA = Path(__file__).parent / "data"
QWEN3 = json.loads((Path(__file__).parents[1] / "configs" /
                    "qwen3-1.7b.json").read_text())["shapes"]


# ------------------------------------------------------------- flops
def test_qwen3_flops_match_a_hand_count():
    # per layer: q 2048*2048, k and v 2048*1024 each, o 2048*2048,
    # gate, up and down 2048*6144 each
    layer = 2048 * 2048 * 2 + 2048 * 1024 * 2 + 3 * 2048 * 6144
    assert layer == 50_331_648
    assert flops.block_params(QWEN3) == 28 * layer
    assert flops.head_params(QWEN3) == 151936 * 2048
    # one decode row at context 2300: 2 * (blocks + head) weights plus
    # 4 * 28 layers * 16 heads * 128 dims * 2300 keys
    per_row = 2 * (28 * layer + 151936 * 2048) + 4 * 28 * 16 * 128 * 2300
    assert flops.decode_step_flops(QWEN3, 8, 2300) == 8 * per_row
    # prefill of 4 x 2048: blocks at every position, head once, causal
    # attention over 2048 * 2049 / 2 query-key pairs
    pre = (2 * 28 * layer * 2048 + 2 * 151936 * 2048
           + 4 * 28 * 16 * 128 * 2048 * 2049 / 2)
    assert flops.prefill_flops(QWEN3, 4, 2048) == pytest.approx(4 * pre)
    assert 2.4e13 < flops.prefill_flops(QWEN3, 4, 2048) < 2.6e13


def test_decode_mfu_reads_means_of_steps_and_program_runs(bench_run):
    reader = bench_run.load_file(bench_run.BENCH / "metrics"
                                 / "decode_mfu.py")
    contexts = [2049, 2050, 2051, 2052]
    work = sum(flops.decode_step_flops(QWEN3, 8, c) for c in contexts) / 4

    def run(seconds, runs):
        return {"trace": {"programs": {"jit_serve_step": [seconds, runs]}},
                "context": {"batch": 8, "decode_contexts": contexts},
                "found": {"config": {"shapes": QWEN3}},
                "device_kind": "TPU v5 lite"}
    want = 100 * work / (0.026 * 197e12)
    assert reader.read(run(4 * 0.026, 4)) == pytest.approx(want)
    # a trace that holds fewer runs than the host's steps reads the same
    assert reader.read(run(3 * 0.026, 3)) == pytest.approx(want)
    assert reader.read({**run(0.1, 4), "trace": None}) is None


# ------------------------------------------------------------- peaks
def test_peak_table_has_v5e_and_refuses_unknown_kinds():
    v5e = peaks.peak("TPU v5 lite")
    assert v5e["bf16_flops"] == 197e12
    assert v5e["hbm_bytes_per_s"] == 819e9
    assert v5e["hbm_bytes"] == 16 * 2**30
    with pytest.raises(KeyError, match="no published peaks"):
        peaks.peak("TPU v9 imaginary")
    with pytest.raises(KeyError):
        peaks.peak("cpu")


# ------------------------------------------------------------- stats
def test_percentile_is_taken_over_every_sample():
    # 100 gaps of one ms and 6 slow ones of 50 ms: the 95th percentile
    # of all 106 samples is slow; a mean of per-batch tails would not be
    samples = [1.0] * 100 + [50.0] * 6
    assert stats.percentile(samples, 95) == 50.0
    assert stats.percentile(list(range(1, 101)), 95) == 95
    assert stats.percentile([3.0], 95) == 3.0
    with pytest.raises(ValueError):
        stats.percentile([], 95)


def test_compile_work_is_summed_up_to_a_time(bench_run):
    counter = bench_run.CompileCounter()
    counter.durations = [("trace", 1.0, 0.25), ("cache_read", 2.0, 0.5),
                         ("cache_read", 3.0, 0.5), ("compile", 9.0, 4.0)]
    assert counter.seconds_before(3.0) == {
        "trace": 0.25, "lower": 0.0, "cache_read": 1.0, "compile": 0.0}
    assert counter.seconds_before(10.0)["compile"] == 4.0


# ------------------------------------------------------------- trace
def test_union_and_idle_stretches():
    iv = [(0.0, 1.0), (0.5, 2.0), (3.0, 4.0)]
    assert union_length(iv) == pytest.approx(3.0)
    assert idle_stretches(iv, 0.0, 5.0) == [(2.0, 3.0), (4.0, 5.0)]
    assert idle_stretches(iv, -1.0, 3.5) == [(-1.0, 0.0), (2.0, 3.0)]


def test_reduce_events_busy_programs_and_gaps_by_span():
    spans = [("traced_window", 0.0, 10.0), ("prefill", 0.0, 2.0),
             ("readback", 2.0, 3.0), ("decode_step", 3.0, 9.0),
             ("readback", 9.0, 10.0)]
    ops = [("fusion.1", 0.5, 2.5), ("fusion.2", 4.0, 8.0),
           ("fusion.1", 8.0, 8.5), ("copy.3", 11.0, 12.0)]
    modules = [("jit_prefill_step(17)", 0.5, 2.5),
               ("jit_serve_step(23)", 4.0, 8.5)]
    red = reduce_events(spans, {"/device:TPU:0": {
        "XLA Ops": ops, "XLA Modules": modules}}, "traced_window")
    assert red["window_s"] == 10.0
    assert red["busy_s"] == pytest.approx(6.5)   # copy.3 lies outside
    assert red["op_events"] == 3
    assert red["programs"] == {"jit_prefill_step": [2.0, 1],
                               "jit_serve_step": [4.5, 1]}
    assert red["ops"][0] == ["fusion.2", 4.0]
    gaps = dict(red["idle_gaps"])
    # idle: 0-0.5 (prefill), 2.5-4 (mid 3.25: decode_step), 8.5-10
    # (mid 9.25: readback)
    assert gaps == pytest.approx({"prefill": 0.5, "decode_step": 1.5,
                                  "readback": 1.5})


def test_reduce_events_averages_over_chips():
    spans = [("traced_window", 0.0, 4.0)]
    chips = {"/device:TPU:0": {"XLA Ops": [("a", 0.0, 4.0)]},
             "/device:TPU:1": {"XLA Ops": [("a", 0.0, 2.0)]}}
    red = reduce_events(spans, chips, "traced_window")
    assert red["busy_s"] == pytest.approx(3.0)
    assert red["idle_gaps"] == [["outside_spans", pytest.approx(1.0)]]


def test_reduce_a_recorded_chip_trace():
    """A tiny decode cell traced on one v5e (``TPU v5 lite``): prefill,
    7 decode steps and their host spans, recorded with the harness."""
    from bench.trace_reduce import read_events, reduce_events

    spans, chips = read_events(DATA / "tiny_decode.xplane.pb",
                               "traced_window",
                               ["gen_prompts", "prefill", "decode_step",
                                "readback"])
    assert list(chips) == ["/device:TPU:0"]
    assert {n for n, _, _ in spans} >= {"traced_window", "prefill",
                                        "decode_step", "readback"}
    red = reduce_events(spans, chips, "traced_window")
    assert red["chips"] == 1
    assert 0 < red["busy_s"] < red["window_s"]
    programs = red["programs"]
    assert programs["jit_prefill_step"][1] == 1
    assert programs["jit_serve_step"][1] == 7
    assert all(v[0] > 0 for v in programs.values())
    # every idle stretch is attributed, and busy plus idle is the window
    idle = sum(s for _, s in red["idle_gaps"])
    assert idle + red["busy_s"] == pytest.approx(red["window_s"], rel=1e-6)
    assert set(dict(red["idle_gaps"])) <= {"gen_prompts", "prefill",
                                           "decode_step", "readback",
                                           "outside_spans"}
    # op names are HLO instruction names, without the ops that hold others
    names = [n for n, _ in red["ops"]]
    assert names and all(" " not in n and not n.startswith("while")
                         for n in names)
