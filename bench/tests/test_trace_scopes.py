"""Splitting each program's device time by named scope
(``bench/trace_scopes.py``) and the per-scope readers
(``bench/metrics/_scopes.py``)."""
from __future__ import annotations

import shutil
from pathlib import Path

import pytest

from bench.trace_reduce import read_events, reduce_events
from bench.trace_scopes import (SCOPES, UNSCOPED, innermost_scope,
                                read_scoped_events, reduce_scopes,
                                scope_seconds)

DATA = Path(__file__).parent / "data"
UNSCOPED_TRACE = DATA / "tiny_decode.xplane.pb"
SCOPED_TRACE = DATA / "tiny_decode_scoped.xplane.pb"
SPANS = ["gen_prompts", "prefill", "decode_step", "readback"]
READERS = {"decode_weight_cast_ms": "weight_cast", "decode_scan_ms": "layers",
           "decode_attn_ms": "attn", "decode_mlp_ms": "mlp",
           "decode_head_ms": "head"}


def test_innermost_scope_is_the_last_scope_before_the_op():
    path = "jit(serve_step)/layers/while/body/closed_call/attn/dot_general:"
    assert innermost_scope(path) == "attn"
    assert innermost_scope("jit(serve_step)/layers/while/body/squeeze:") \
        == "layers"
    # the op's own name is not a scope, nor is a jitted function's name
    assert innermost_scope("jit(serve_step)/while/body/head") is None
    assert innermost_scope("jit(head)/dot_general:") is None
    assert innermost_scope(None) is None and innermost_scope("") is None


def test_reduce_scopes_self_time_unscoped_containers_and_chips():
    body = "jit(serve_step)/layers/while/body/"
    chip0 = {
        "modules": [("jit_serve_step(7)", 1.0, 5.0),
                    ("jit_prefill_step(8)", 9.0, 11.0)],
        "ops": [("fusion.1", body + "attn/dot_general:", 1.0, 2.0),
                ("fusion.2", body + "dynamic_slice:", 2.0, 2.5),
                # a while op holds the others: left out
                ("while.3", "jit(serve_step)/layers/while", 1.0, 4.0),
                ("copy-done", None, 2.5, 3.0),           # no scope
                ("fusion.4", "jit(serve_step)/weight_cast/convert:", 3.0,
                 4.0),
                ("fusion.5", "jit(serve_step)/head/argmax:", 4.5, 5.0),
                # clipped at the window's end
                ("fusion.6", "jit(prefill_step)/mlp/dot_general:", 9.5,
                 10.5),
                ("fusion.7", "jit(prefill_step)/mlp/dot_general:", 10.5,
                 11.0)]}
    chip1 = {"modules": [("jit_serve_step(7)", 1.0, 5.0)],
             "ops": [("fusion.1", body + "attn/dot_general:", 1.0, 3.0)]}
    split = reduce_scopes((0.0, 10.0), {"/device:TPU:0": chip0,
                                        "/device:TPU:1": chip1})
    # chip 0: attn 1, layers 0.5, weight_cast 1, head 0.5, and 1 s of the
    # 4 s run unscoped (the copy and the idle stretch 4-4.5); chip 1:
    # attn 2, unscoped 2; the mean over both
    assert split["jit_serve_step"] == pytest.approx(
        {"attn": 1.5, "layers": 0.25, "weight_cast": 0.5, "head": 0.25,
         UNSCOPED: 1.5})
    assert sum(split["jit_serve_step"].values()) == pytest.approx(4.0)
    # 1 s of chip 0's prefill run lies in the window: 0.5 s of mlp,
    # clipped, and the 0.5 s before it; halved by the mean over chips
    assert split["jit_prefill_step"] == pytest.approx(
        {"mlp": 0.25, UNSCOPED: 0.25})


def test_decoder_reads_a_recorded_trace_as_profile_data_does():
    win, chips = read_scoped_events(UNSCOPED_TRACE, "traced_window")
    spans, pd_chips = read_events(UNSCOPED_TRACE, "traced_window", SPANS)
    assert list(chips) == list(pd_chips) == ["/device:TPU:0"]
    want = [(s, e) for n, s, e in spans if n == "traced_window"][0]
    # ProfileData gives whole nanoseconds; the decoder keeps picoseconds
    assert win == pytest.approx(want, abs=2e-9)
    mine = sorted((s, e, n) for n, s, e in chips["/device:TPU:0"]["modules"])
    theirs = sorted((s, e, n) for n, s, e in
                    pd_chips["/device:TPU:0"]["XLA Modules"])
    assert [n for _, _, n in mine] == [n for _, _, n in theirs]
    assert [v for s, e, _ in mine for v in (s, e)] == pytest.approx(
        [v for s, e, _ in theirs for v in (s, e)], abs=2e-9)
    ops = chips["/device:TPU:0"]["ops"]
    assert len(ops) == len(pd_chips["/device:TPU:0"]["XLA Ops"])
    assert any(p and p.startswith("jit(serve_step)/") for _, p, _, _ in ops)


def _run(tmp_path: Path, trace: Path) -> dict:
    """A traced run's reader input, its trace where ``bench/run.py``'s
    tracer leaves it beside ``bench/``."""
    where = tmp_path / ".bench_trace" / "plugins" / "profile" / "1"
    where.mkdir(parents=True)
    shutil.copy(trace, where / "host.xplane.pb")
    spans, chips = read_events(trace, "traced_window", SPANS)
    return {"found": {"bench": tmp_path / "bench"},
            "trace": reduce_events(spans, chips, "traced_window")}


def _read(bench_run, name: str, run: dict):
    reader = bench_run.load_file(bench_run.BENCH / "metrics" / f"{name}.py")
    return reader.read(run)


def test_readers_return_none_for_a_program_without_scopes(tmp_path,
                                                          bench_run):
    """The parent program, traced before it had scopes: all of its
    time is unscoped, so no reader has anything to read."""
    split = scope_seconds(tmp_path, "traced_window")
    assert split is None                      # no trace there at all
    run = _run(tmp_path, UNSCOPED_TRACE)
    _, split = scope_seconds(tmp_path / ".bench_trace", "traced_window")
    assert set(split["jit_serve_step"]) == {UNSCOPED}
    for name in [*READERS, "prefill_attn_ms", "prefill_mlp_ms"]:
        assert _read(bench_run, name, run) is None
    # and none where the run was not traced on a chip
    assert _read(bench_run, "decode_attn_ms", {**run, "trace": None}) is None


def test_reduce_a_recorded_scoped_chip_trace(tmp_path, bench_run):
    """The tiny decode cell of the unscoped trace, recorded on one v5e
    (``TPU v5 lite``) with the scoped program: prefill and 7 decode
    steps."""
    win, chips = read_scoped_events(SCOPED_TRACE, "traced_window")
    split = reduce_scopes(win, chips)
    run = _run(tmp_path, SCOPED_TRACE)
    programs = run["trace"]["programs"]
    serve = split["jit_serve_step"]
    assert set(serve) == set(SCOPES) | {UNSCOPED}
    # every op of the step programs that has no scope is one XLA adds:
    # an async copy or a buffer allocation.  At this size they are a
    # sixth of a 16 us step; at the cells' size, under 0.1%
    for name, path, _, _ in chips["/device:TPU:0"]["ops"]:
        if path is None or path.startswith(("jit(serve_step)",
                                            "jit(prefill_step)")):
            assert innermost_scope(path) or name.startswith(
                ("copy-start", "copy-done", "custom-call", "while")), name
    assert serve[UNSCOPED] < 0.2 * programs["jit_serve_step"][0]
    # a program's scopes sum to its device time (ProfileData's, in whole
    # nanoseconds at each end of each run)
    for name, seconds in split.items():
        time, runs = programs[name]
        assert sum(seconds.values()) == pytest.approx(time, abs=2e-9 * runs)
    assert set(split["jit_prefill_step"]) >= {"embed", "weight_cast", "attn",
                                              "mlp", "head"}
    # each reader gives its scope's time per run of the program, in ms
    runs = programs["jit_serve_step"][1]
    for name, scope in READERS.items():
        assert _read(bench_run, name, run) == pytest.approx(
            1e3 * serve[scope] / runs)
    assert _read(bench_run, "prefill_attn_ms", run) == pytest.approx(
        1e3 * split["jit_prefill_step"]["attn"])
    # a trace on disk that is not the run's own is not read
    other = {"found": run["found"],
             "trace": {**run["trace"],
                       "window_s": run["trace"]["window_s"] + 1}}
    assert _read(bench_run, "decode_attn_ms", other) is None
