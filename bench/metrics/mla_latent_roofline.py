"""mla_latent_roofline: the MLA decode's latent attention's share of
its roofline, in %: the larger of the live latent cache's bytes over
the HBM peak and the absorbed form's FLOPs over the bf16 peak
(``bench/flops_mla.latent_bytes``, ``latent_flops``; means over the
traced steps, each at its live context), over the time
``mla_latent_ms`` measured.  None where that reads nothing."""
from __future__ import annotations

from bench import flops_mla, peaks
from bench.metrics.mla_latent_ms import read as latent_ms


def read(run: dict) -> float | None:
    ms = latent_ms(run)
    contexts = run["context"].get("decode_contexts", [])
    if ms is None or not contexts:
        return None
    shapes = run["found"]["config"]["shapes"]
    batch = run["context"]["batch"]
    moved = sum(flops_mla.latent_bytes(shapes, batch, c)
                for c in contexts) / len(contexts)
    work = sum(flops_mla.latent_flops(shapes, batch, c)
               for c in contexts) / len(contexts)
    peak = peaks.peak(run["device_kind"])
    bound = max(moved / peak["hbm_bytes_per_s"], work / peak["bf16_flops"])
    return 100.0 * bound / (ms * 1e-3)
