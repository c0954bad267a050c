"""The ``minicpm3-4b.decode`` cell at tiny size on the CPU, through the
code a chip run takes minus the look for a chip: its contract line,
traced and untraced; the check that decides ``correct`` failing a broken
timed path (an altered token, a cache the step does not keep, a rewind
to the wrong length) and the fp8 control; and its four per-layer
readers on synthetic events and on a recorded chip trace."""
from __future__ import annotations

import json
import shutil
from pathlib import Path

import jax
import numpy as np
import pytest

from bench import flops_mla
from bench.spans import Spans
from bench.trace_reduce import read_events, reduce_events

CELL = "minicpm3-4b.decode"
SEED = 2**31 + 5
TINY_MLA = dict(hidden_size=64, intermediate_size=128, num_hidden_layers=2,
                num_attention_heads=4, num_key_value_heads=4, q_lora_rank=32,
                kv_lora_rank=16, qk_nope_head_dim=16, qk_rope_head_dim=8,
                v_head_dim=16, vocab_size=512, dim_model_base=16)
TINY_SESSIONS = dict(sessions=2, document_len=32, answer_len=8)
# tiny-size limits: sound tiny runs read gaps under 7e-5 and logit
# errors of 1.2-2.2e-2 (bfloat16 against float32, CPU, seeds 1-8); the
# fp8 control reads logit errors of 0.12-0.21, and an altered token a
# gap of the logits' own spread, about 5e-3
TINY_GAP_LIMIT = 5e-4
TINY_REL_LIMIT = 6e-2
DATA = Path(__file__).parent / "data"
READERS = ("mla_decode_mfu", "mla_weight_stream_roofline", "mla_latent_ms",
           "mla_latent_roofline")


@pytest.fixture
def tiny(bench_run):
    """The cell as ``find_cell`` finds it, cut to tiny sizes, with the
    tiny limits and every other answer token's logits kept."""
    found = bench_run.find_cell(CELL)
    found["config"]["shapes"].update(TINY_MLA)
    found["traffic"].update(TINY_SESSIONS)
    found["limits"]["keep_logits_every"] = 2
    numbers = found["limits"]["numbers"]
    numbers["served_logit_gap"]["limit"] = TINY_GAP_LIMIT
    numbers["logit_rel_err"]["limit"] = TINY_REL_LIMIT
    return found


def _execute(bench_run, found, tmp_path, trace=False, control=False):
    return bench_run.execute(found, SEED, 1.0, trace,
                             trace_dir=tmp_path / "trace", control=control)


@pytest.mark.parametrize("trace", [False, True])
def test_tiny_run_prints_the_contract_line(bench_run, tiny, tmp_path, trace):
    result = _execute(bench_run, tiny, tmp_path, trace)
    json.dumps(result)
    assert result["correct"] is True, result["checks"]
    assert result["failed"] == 0 and result["attempted"] > 0
    assert list(result)[-1] == "checks"
    assert ("breakdown" in result) == trace
    counts = result["diagnostics"]["counts"]
    # rounds after the first ask each document again from a rewound cache
    assert counts["finished"] >= 2
    if trace:
        assert set(result["metrics"]) <= {m["name"] for m in
                                          tiny["per_layer"]}
        assert set(READERS) <= {m["name"] for m in tiny["per_layer"]}
        assert result["diagnostics"]["traced"]["decode_contexts"] == 8
    else:
        assert set(result["metrics"]) == {"decode_tok_s", "itl_ms_p95",
                                          "setup_s"}
    phases = result["diagnostics"]["setup_phases"]
    assert {"setup.weights", "setup.prefill", "setup.warmup",
            "jax.compile"} <= set(phases)
    assert result["checks"]["compiles_in_window"]["value"] == 0


def _broken_decode(monkeypatch, wrap):
    from repro.launch import steps

    make = steps.make_decode_step

    def broken(arch, rt, policy):
        return wrap(arch, make(arch, rt, policy))

    monkeypatch.setattr(steps, "make_decode_step", broken)


def test_token_altered_in_decode_step_is_caught(bench_run, tiny, tmp_path,
                                                monkeypatch):
    def wrap(arch, step):
        def serve_step(params, cache, tokens):
            nxt, logits, cache = step(params, cache, tokens)
            return (nxt + 1) % arch.vocab, logits, cache
        return serve_step

    _broken_decode(monkeypatch, wrap)
    result = _execute(bench_run, tiny, tmp_path)
    assert result["correct"] is False
    gap = result["checks"]["served_logit_gap"]
    assert gap["value"] > gap["limit"]


def test_decode_step_that_returns_its_cache_unchanged_is_caught(
        bench_run, tiny, tmp_path, monkeypatch):
    def wrap(arch, step):
        def serve_step(params, cache, tokens):
            nxt, logits, _ = step(params, cache, tokens)
            return nxt, logits, cache
        return serve_step

    _broken_decode(monkeypatch, wrap)
    assert _execute(bench_run, tiny, tmp_path)["correct"] is False


@pytest.mark.parametrize("off", [-1, 1])
def test_rewind_to_the_wrong_length_is_caught(bench_run, tiny, tmp_path,
                                              monkeypatch, off):
    """Each round starts one position before or after the document's
    end: the question overwrites the document's last token, or sees a
    position no token was written to."""
    load = bench_run.load_file

    def load_shifted(path, name=None):
        mod = load(path, name)
        if path.name == "sessions.py":
            init = mod.Server.__init__

            def shifted(self, *args, **kwargs):
                init(self, *args, **kwargs)
                self.rewind = jax.device_put(np.int32(self.document_len
                                                      + off))
            mod.Server.__init__ = shifted
        return mod

    monkeypatch.setattr(bench_run, "load_file", load_shifted)
    result = _execute(bench_run, tiny, tmp_path)
    assert result["correct"] is False


def test_fp8_control_in_the_programs_place_reads_incorrect(bench_run, tiny,
                                                           tmp_path):
    result = _execute(bench_run, tiny, tmp_path, control=True)
    assert result["correct"] is False
    err = result["checks"]["logit_rel_err"]
    assert err["value"] > err["limit"]


@pytest.mark.parametrize("seed", [1, 2])
def test_control_script_judges_both_sides_by_the_run_check(bench_run, tiny,
                                                            seed):
    ctl = bench_run.load_file(bench_run.ROOT / "bench" / "control.py")
    read = ctl.readings(tiny, seed, Spans())
    assert read["program_correct"] is True, read
    assert read["control_correct"] is False, read


# ---------------------------------------------------------------- readers
CFG = dict(hidden_size=8, intermediate_size=16, num_hidden_layers=3,
           num_attention_heads=2, q_lora_rank=4, kv_lora_rank=6,
           qk_nope_head_dim=3, qk_rope_head_dim=2, v_head_dim=3,
           vocab_size=32)


def test_mla_counts_by_hand():
    # a layer: q_a 8x4, kv_a 8x(6+2), q_b 4x(2*(3+2)), k_b 6x(2*3),
    # v_b 6x(2*3), o (2*3)x8, gate and up 8x16, down 16x8
    layer = 32 + 64 + 40 + 36 + 36 + 48 + 3 * 128
    assert flops_mla.block_params(CFG) == 3 * layer
    streamed = layer - 36 - 36
    # a decode step of 5 rows at a live context of 7: two FLOPs a weight
    # and of the head's 32x8, and per key and layer 2*2*(3+2) + 2*2*3
    want = 5 * (2 * (3 * layer + 256) + 3 * (20 + 12) * 7)
    assert flops_mla.decode_step_flops(CFG, 5, 7) == want
    # the kernel's rows, each call's input and outputs: q_a/kv_a 8 in,
    # 4+8 out; q_b 4, 10; o 6, 8; gate/up 8, 32; down 16, 8
    rows = (8 + 12) + (4 + 10) + (6 + 8) + (8 + 32) + (16 + 8)
    assert flops_mla.stream_bytes(CFG, 5) == \
        4 * 3 * streamed + 2 * 3 * 5 * rows
    # the latent cache: c_kv 6 and the rope key 2, bf16, per position
    assert flops_mla.latent_bytes(CFG, 5, 7) == 2 * 3 * 5 * 7 * 8
    # per position 2*2*(6+2+6), per row the absorb 2*2*3*6 and the
    # up-projection 2*2*6*3
    assert flops_mla.latent_flops(CFG, 5, 7) == \
        3 * 5 * (56 * 7 + 72 + 72)


def _synthetic_run(bench_run, chips, window=(0.0, 10.0), runs=2,
                   program_s=4.0) -> dict:
    return {"found": {"bench": bench_run.BENCH,
                      "config": {"shapes": CFG}},
            "context": {"batch": 5, "decode_contexts": [6, 8]},
            "device_kind": "TPU v5 lite",
            "trace": {"window_s": window[1] - window[0],
                      "programs": {"jit_serve_step": [program_s, runs]}},
            "scoped_events": (window, chips)}


BODY = "jit(serve_step)/layers/while/body/attn/"
CHIP = {"modules": [("jit_serve_step(3)", 1.0, 3.0),
                    ("jit_serve_step(3)", 4.0, 6.0),
                    ("jit_prefill_step(4)", 7.0, 9.0)],
        "ops": [("fusion.1", BODY + "latent/dot_general:", 1.0, 1.5),
                ("fusion.2", BODY + "latent/reduce_max:", 4.0, 4.25),
                ("while.3", "jit(serve_step)/layers/while", 1.0, 3.0),
                ("fusion.4", BODY + "dot_general:", 1.5, 2.0),
                ("weight_stream.5", BODY + "weight_stream/pallas_call",
                 2.0, 2.5),
                ("weight_stream.6", BODY + "weight_stream/pallas_call",
                 4.5, 5.0),
                # a path that only names the scope as its own op's name
                ("fusion.7", "jit(serve_step)/attn/latent", 5.0, 5.5),
                # the scope in another program
                ("fusion.8", "jit(prefill_step)/attn/latent/dot:", 7.0,
                 8.0)]}


def test_readers_on_synthetic_events(bench_run):
    read = {n: bench_run.load_file(bench_run.BENCH / "metrics" / f"{n}.py")
            .read for n in READERS}
    run = _synthetic_run(bench_run, {"/device:TPU:0": CHIP})
    # latent: 0.5 + 0.25 s over 2 runs of the step program
    assert read["mla_latent_ms"](run) == pytest.approx(375.0)
    mean = lambda f: (f(CFG, 5, 6) + f(CFG, 5, 8)) / 2   # noqa: E731
    bound = max(mean(flops_mla.latent_bytes) / 819e9,
                mean(flops_mla.latent_flops) / 197e12)
    assert read["mla_latent_roofline"](run) == pytest.approx(
        100 * bound / 0.375)
    # the kernel: 0.5 + 0.5 s over 2 runs
    assert read["mla_weight_stream_roofline"](run) == pytest.approx(
        100 * flops_mla.stream_bytes(CFG, 5) / (0.5 * 819e9))
    # model FLOPs over 2 s a run
    assert read["mla_decode_mfu"](run) == pytest.approx(
        100 * mean(flops_mla.decode_step_flops) / (2.0 * 197e12))


def test_readers_read_nothing_without_the_scope_kernel_or_program(
        bench_run):
    read = {n: bench_run.load_file(bench_run.BENCH / "metrics" / f"{n}.py")
            .read for n in READERS}
    bare = {"modules": CHIP["modules"],
            "ops": [op for op in CHIP["ops"]
                    if "latent/" not in (op[1] or "")
                    and not op[0].startswith("weight_stream")]}
    run = _synthetic_run(bench_run, {"/device:TPU:0": bare})
    assert read["mla_latent_ms"](run) is None
    assert read["mla_latent_roofline"](run) is None
    assert read["mla_weight_stream_roofline"](run) is None
    assert read["mla_decode_mfu"](run) is not None
    untraced = dict(run, trace={"window_s": 10.0, "programs": {}})
    assert all(r(untraced) is None for r in read.values())


def test_readers_on_a_recorded_chip_trace_of_a_gqa_program(tmp_path,
                                                            bench_run):
    """The tiny qwen3 decode cell recorded on one v5e with the
    weight-streaming kernel and no latent attention: the latent readers
    find no op in the scope; the kernel's reader reads the trace beside
    ``bench/`` (here counting MLA bytes against the GQA program's time:
    only that it reads is checked)."""
    trace = DATA / "tiny_decode_stream.xplane.pb"
    where = tmp_path / ".bench_trace" / "plugins" / "profile" / "1"
    where.mkdir(parents=True)
    shutil.copy(trace, where / "host.xplane.pb")
    spans, chips = read_events(trace, "traced_window",
                               ["gen_prompts", "prefill", "decode_step",
                                "readback"])
    run = {"found": {"bench": tmp_path / "bench",
                     "config": {"shapes": CFG}},
           "context": {"batch": 2, "decode_contexts": [40, 41]},
           "device_kind": "TPU v5 lite",
           "trace": reduce_events(spans, chips, "traced_window")}
    read = {n: bench_run.load_file(bench_run.BENCH / "metrics" / f"{n}.py")
            .read for n in READERS}
    assert read["mla_latent_ms"](run) is None
    assert read["mla_latent_roofline"](run) is None
    assert read["mla_weight_stream_roofline"](run) > 0
    # a trace on disk that is not the run's own is not read
    other = {k: v for k, v in run.items() if k != "scoped_events"}
    other["trace"] = {**run["trace"], "window_s": run["trace"]["window_s"] + 1}
    assert read["mla_weight_stream_roofline"](other) is None
