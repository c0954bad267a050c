"""minicpm3-4b [dense] — MLA attention (hf:openbmb/MiniCPM3-4B)."""
import math

from repro.configs.base import ArchConfig

ARCH = ArchConfig(
    name="minicpm3-4b", family="dense",
    n_layers=62, d_model=2560, n_heads=40, n_kv_heads=40,
    d_ff=6400, vocab=73448, head_dim=64,
    attn_type="mla", q_lora_rank=768, kv_lora_rank=256, rope_head_dim=32,
    act="silu", gated_mlp=True, tie_embeddings=True,
    # muP: scale_emb; scale_depth / sqrt(num_hidden_layers), of the
    # published depth also where fewer layers are held (a pipeline's
    # stage); hidden_size / dim_model_base
    embed_scale=12.0, residual_scale=1.4 / math.sqrt(62),
    head_divisor=2560 / 256,
)
