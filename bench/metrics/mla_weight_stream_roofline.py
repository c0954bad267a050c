"""mla_weight_stream_roofline: the weight-streaming kernel's share of
the HBM roofline in an MLA model's decode step program
(``jit_serve_step``), in %.

Bytes: the float32 stacks the kernel reads (q_a, kv_a, q_b, o and the
gated MLP; not W_UK and W_UV, which the latent attention applies per
head) and the bfloat16 rows of its five calls a layer
(``bench/flops_mla.stream_bytes``).  Time: the device time of the ops
named ``weight_stream.<n>`` inside runs of the program, per run
(``kernel_seconds`` of ``decode_weight_stream_roofline``).  None where
the trace holds no run of the program or no op of the kernel, or is not
the run's own."""
from __future__ import annotations

from bench import flops_mla, peaks
from bench.metrics._events import scoped_events
from bench.metrics._program import program_seconds
from bench.metrics.decode_weight_stream_roofline import kernel_seconds

PROGRAM = "jit_serve_step"
KERNEL = "weight_stream"


def read(run: dict) -> float | None:
    found = program_seconds(run, PROGRAM)
    events = None if found is None else scoped_events(run)
    if events is None:
        return None
    seconds = kernel_seconds(events[1], events[0], PROGRAM, KERNEL)
    if seconds <= 0:
        return None
    moved = flops_mla.stream_bytes(run["found"]["config"]["shapes"],
                                   run["context"]["batch"])
    bandwidth = peaks.peak(run["device_kind"])["hbm_bytes_per_s"]
    return 100.0 * moved / (seconds / found[1] * bandwidth)
