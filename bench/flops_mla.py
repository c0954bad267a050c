"""Model FLOPs and bytes of a decode step of a MiniCPM3-style decoder
with multi-head latent attention (MLA), from its configuration's shapes
(the Hugging Face ``config.json`` keys of ``bench/configs/<config>.json``).

Model FLOPs are counted as ``bench/flops.py`` counts a dense decoder's:
two per weight of every matrix a token passes through (the MLA
projections, the up-projections of the new token's key and value, the
output projection, the gated MLP and the head), plus attention as the
model defines it: scores over ``qk_nope_head_dim + qk_rope_head_dim``
and the value sum over ``v_head_dim``, per head, per live position.

The absorbed form the decode step computes, and the bytes it moves,
are counted apart: the weight-streaming kernel's float32 stacks and
bfloat16 rows, and the latent attention's cache reads and FLOPs.
"""
from __future__ import annotations

WEIGHT_BYTES = 4          # float32 weights (departures.params_dtype)
ROW_BYTES = 2             # bfloat16 activations and cache
# the matrices the weight-streaming kernel reads as whole layer stacks;
# wk_b and wv_b are applied per head, in the latent attention
STREAMED = ("wq_a", "wkv_a", "wq_b", "wo", "w_gate", "w_up", "w_down")


def _dims(cfg: dict) -> dict[str, int]:
    return dict(
        L=cfg["num_hidden_layers"], d=cfg["hidden_size"],
        H=cfg["num_attention_heads"], qr=cfg["q_lora_rank"],
        kvr=cfg["kv_lora_rank"], nope=cfg["qk_nope_head_dim"],
        r=cfg["qk_rope_head_dim"], vd=cfg["v_head_dim"],
        ff=cfg["intermediate_size"], V=cfg["vocab_size"])


def layer_matrices(cfg: dict) -> dict[str, tuple[int, int]]:
    """``(in, out)`` of each matrix of one decoder layer."""
    k = _dims(cfg)
    d, H, qr, kvr, nope, r, vd, ff = (k[n] for n in (
        "d", "H", "qr", "kvr", "nope", "r", "vd", "ff"))
    return {"wq_a": (d, qr), "wkv_a": (d, kvr + r),
            "wq_b": (qr, H * (nope + r)), "wk_b": (kvr, H * nope),
            "wv_b": (kvr, H * vd), "wo": (H * vd, d),
            "w_gate": (d, ff), "w_up": (d, ff), "w_down": (ff, d)}


def block_params(cfg: dict, names=None) -> int:
    """Weights of the matrices ``names`` (all by default) in all
    decoder layers."""
    mats = layer_matrices(cfg)
    return cfg["num_hidden_layers"] * sum(
        i * o for n, (i, o) in mats.items() if names is None or n in names)


def head_params(cfg: dict) -> int:
    """Weights of the output projection onto the vocabulary."""
    return cfg["vocab_size"] * cfg["hidden_size"]


def attention_flops(cfg: dict, context: int) -> float:
    """Scores and value sum of one query token over ``context`` keys,
    all layers, as the model defines them."""
    k = _dims(cfg)
    per_key = 2 * k["H"] * (k["nope"] + k["r"]) + 2 * k["H"] * k["vd"]
    return float(k["L"] * per_key * context)


def decode_step_flops(cfg: dict, batch: int, context: int) -> float:
    """Model FLOPs of one decode step of ``batch`` rows, each attending
    to ``context`` positions (its prompt, its tokens so far and the new
    one)."""
    per_token = 2.0 * (block_params(cfg) + head_params(cfg))
    return batch * (per_token + attention_flops(cfg, context))


def stream_bytes(cfg: dict, batch: int) -> float:
    """Bytes the weight-streaming kernel moves in one decode step: the
    float32 stacks of ``STREAMED``, and the bfloat16 rows each of a
    layer's five calls reads and writes (q_a with kv_a; q_b; o; gate
    with up; down)."""
    mats = layer_matrices(cfg)
    calls = (("wq_a", "wkv_a"), ("wq_b",), ("wo",), ("w_gate", "w_up"),
             ("w_down",))
    rows = sum(mats[c[0]][0] + sum(mats[n][1] for n in c) for c in calls)
    return (WEIGHT_BYTES * block_params(cfg, STREAMED)
            + ROW_BYTES * cfg["num_hidden_layers"] * batch * rows)


def latent_bytes(cfg: dict, batch: int, context: int) -> float:
    """Bytes of the live latent cache a decode step reads: ``c_kv`` and
    the rope key, bfloat16, per live position, per row, per layer."""
    k = _dims(cfg)
    return float(ROW_BYTES * k["L"] * batch * context * (k["kvr"] + k["r"]))


def latent_flops(cfg: dict, batch: int, context: int) -> float:
    """FLOPs of the absorbed attention in one decode step: per live
    position the latent scores, the rope-key scores and the latent
    context, and per row the query's absorption by W_UK and the
    context's up-projection by W_UV, per head, per layer."""
    k = _dims(cfg)
    H, kvr, r = k["H"], k["kvr"], k["r"]
    per_position = 2 * H * (kvr + r + kvr)
    per_row = 2 * H * k["nope"] * kvr + 2 * H * kvr * k["vd"]
    return float(k["L"] * batch * (per_position * context + per_row))
