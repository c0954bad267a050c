"""Model FLOPs of a dense decoder-only transformer, from its
configuration's shapes (the Hugging Face ``config.json`` keys the
configuration files under ``bench/configs/`` hold).

Counted as the serving roofline counts them: two FLOPs per weight of
every matrix multiplication a token passes through (``2 * N`` per
token), plus the attention scores and the weighted sum of the values
over the context the token attends to.  The embedding lookup is a
gather and adds none.  Only the work a request needs counts: causal
attention over the live context, not the padded cache, and the output
head once per sequence in a prefill that returns the last position's
logits.
"""
from __future__ import annotations


def _dims(cfg: dict) -> tuple[int, int, int, int, int, int, int]:
    d = cfg["hidden_size"]
    hq = cfg["num_attention_heads"]
    hkv = cfg["num_key_value_heads"]
    hd = cfg.get("head_dim") or d // hq
    return (cfg["num_hidden_layers"], d, hq, hkv, hd,
            cfg["intermediate_size"], cfg["vocab_size"])


def block_params(cfg: dict) -> int:
    """Weights of the matrix multiplications in all decoder layers:
    q, k, v and o projections and the gated MLP (gate, up, down)."""
    n_layers, d, hq, hkv, hd, ff, _ = _dims(cfg)
    attn = d * hq * hd + 2 * d * hkv * hd + hq * hd * d
    return n_layers * (attn + 3 * d * ff)


def head_params(cfg: dict) -> int:
    """Weights of the output projection onto the vocabulary."""
    _, d, _, _, _, _, vocab = _dims(cfg)
    return vocab * d


def attention_flops(cfg: dict, context: int) -> float:
    """Scores and value sum of one query token over ``context`` keys,
    all layers: 2 FLOPs per multiply-add, two contractions of head_dim
    per key per query head."""
    n_layers, _, hq, _, hd, _, _ = _dims(cfg)
    return 4.0 * n_layers * hq * hd * context


def decode_step_flops(cfg: dict, batch: int, context: int) -> float:
    """One decode step of ``batch`` rows, each attending to ``context``
    positions (its prompt, its tokens so far and the new one)."""
    per_token = 2.0 * (block_params(cfg) + head_params(cfg))
    return batch * (per_token + attention_flops(cfg, context))


def prefill_flops(cfg: dict, batch: int, prompt_len: int) -> float:
    """Prefill of ``batch`` prompts of ``prompt_len`` tokens with causal
    attention, returning the logits of the last position only."""
    s = prompt_len
    dense = 2.0 * block_params(cfg) * s + 2.0 * head_params(cfg)
    # query i attends to i + 1 keys: sum over i of (i + 1) = s (s + 1) / 2
    attn = attention_flops(cfg, 1) * s * (s + 1) / 2
    return batch * (dense + attn)
