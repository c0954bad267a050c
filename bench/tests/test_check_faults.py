"""The check that decides ``correct`` fails a broken timed path: a run
at tiny size on the CPU, with the program broken underneath the driver,
reads ``correct`` false; and so does a run whose check judges the
lower-precision control in the program's place."""
from __future__ import annotations

import numpy as np
import pytest

from bench.spans import Spans

SEED = 2**31 + 3
# tiny-size limits: sound tiny runs read gaps under 2e-4 and logit
# errors under 1e-2 (bfloat16 against float32, CPU, seeds 1-6); an
# altered token reads a gap of the logits' own spread, about 1e-2, and
# the fp8 control a logit error of 6e-2 or more
TINY_GAP_LIMIT = 1e-3
TINY_REL_LIMIT = 3e-2


def _run(bench_run, found, tmp_path, control=False):
    numbers = found["limits"]["numbers"]
    numbers["served_logit_gap"]["limit"] = TINY_GAP_LIMIT
    numbers["logit_rel_err"]["limit"] = TINY_REL_LIMIT
    return bench_run.execute(found, SEED, 1.0, False,
                             trace_dir=tmp_path / "trace", control=control)


@pytest.mark.parametrize("cell", ["qwen3-1.7b.decode", "qwen3-1.7b.prefill"])
def test_sound_tiny_serve_run_is_correct(bench_run, tiny, tmp_path, cell):
    assert _run(bench_run, tiny(cell), tmp_path)["correct"] is True


def test_token_altered_in_decode_step_is_caught(bench_run, tiny, tmp_path,
                                                monkeypatch):
    from repro.launch import steps

    make = steps.make_decode_step

    def broken(arch, rt, policy):
        step = make(arch, rt, policy)

        def serve_step(params, cache, tokens):
            nxt, logits, cache = step(params, cache, tokens)
            return (nxt + 1) % arch.vocab, logits, cache
        return serve_step

    monkeypatch.setattr(steps, "make_decode_step", broken)
    result = _run(bench_run, tiny("qwen3-1.7b.decode"), tmp_path)
    assert result["correct"] is False
    gap = result["checks"]["served_logit_gap"]
    assert gap["value"] > gap["limit"]


def test_decode_step_that_returns_its_cache_unchanged_is_caught(
        bench_run, tiny, tmp_path, monkeypatch):
    from repro.launch import steps

    make = steps.make_decode_step

    def broken(arch, rt, policy):
        step = make(arch, rt, policy)

        def serve_step(params, cache, tokens):
            nxt, logits, _ = step(params, cache, tokens)
            return nxt, logits, cache
        return serve_step

    monkeypatch.setattr(steps, "make_decode_step", broken)
    found = tiny("qwen3-1.7b.decode")
    found["limits"]["keep_logits_every"] = 2
    result = _run(bench_run, found, tmp_path)
    assert result["correct"] is False


def test_token_altered_in_prefill_is_caught(bench_run, tiny, tmp_path,
                                            monkeypatch):
    from repro.launch import steps

    make = steps.make_prefill_step

    def broken(arch, rt, policy, cache_len):
        step = make(arch, rt, policy, cache_len)

        def prefill_step(params, batch):
            logits, cache = step(params, batch)
            # every request's best logit moves to the next id
            return jnp.roll(logits, 1, -1), cache
        return prefill_step

    import jax.numpy as jnp

    monkeypatch.setattr(steps, "make_prefill_step", broken)
    result = _run(bench_run, tiny("qwen3-1.7b.prefill"), tmp_path)
    assert result["correct"] is False


@pytest.mark.parametrize("cell", ["qwen3-1.7b.decode", "qwen3-1.7b.prefill"])
def test_fp8_control_in_the_programs_place_reads_incorrect(
        bench_run, tiny, tmp_path, cell):
    """A whole run with the reference's fp8 control judged in the
    program's place, through the check a run applies, reads false."""
    result = _run(bench_run, tiny(cell), tmp_path, control=True)
    assert result["correct"] is False
    err = result["checks"]["logit_rel_err"]
    assert err["value"] > err["limit"]


@pytest.mark.parametrize("seed", [1, 2, 3])
def test_control_script_judges_both_sides_by_the_run_check(bench_run, tiny,
                                                            seed):
    """``control.py``'s readings of one seed: the program passes and the
    fp8 control fails the limits a run's check applies."""
    ctl = bench_run.load_file(bench_run.ROOT / "bench" / "control.py")
    found = tiny("qwen3-1.7b.decode")
    found["limits"]["keep_logits_every"] = 2
    numbers = found["limits"]["numbers"]
    numbers["served_logit_gap"]["limit"] = TINY_GAP_LIMIT
    numbers["logit_rel_err"]["limit"] = TINY_REL_LIMIT
    read = ctl.readings(found, seed, Spans())
    assert read["program_correct"] is True, read
    assert read["control_correct"] is False, read
    assert read["control"]["logit_rel_err"] > TINY_REL_LIMIT


def test_judge_holds_each_number_to_its_own_limit(bench_run):
    driver = bench_run.load_file(bench_run.ROOT / "bench" / "drivers"
                                 / "serve.py")
    limits = {"numbers": {"served_logit_gap": {"limit": 0.01},
                          "logit_rel_err": {"limit": 0.1}}}
    ok = driver.judge({"gap": np.array([[0.0, 0.01]]),
                       "rel_err": np.array([0.1])}, limits)
    assert all(c["ok"] for c in ok)
    bad = driver.judge({"gap": np.array([[0.0, 0.02]]),
                        "rel_err": np.array([0.05])}, limits)
    assert [c["name"] for c in bad if not c["ok"]] == ["served_logit_gap"]
