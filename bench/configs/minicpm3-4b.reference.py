"""Plain float32 reference of a MiniCPM3 decoder, and its weights.

Follows the published MiniCPM3 layer equations (Hugging Face
``modeling_minicpm``, MiniCPM3): RMSNorm before attention and MLP;
multi-head latent attention, the query through a rank-``q_lora_rank``
bottleneck with its own RMSNorm, keys and values up-projected from a
normed rank-``kv_lora_rank`` latent, with a rotary key of
``qk_rope_head_dim`` shared by all heads; scores scaled by
1/sqrt(qk_nope_head_dim + qk_rope_head_dim); a SiLU-gated MLP; and the
muP scalings: the token embedding times ``scale_emb``, every residual
branch times ``scale_depth / sqrt(published depth)``, and the last
hidden state, after the final RMSNorm, divided by ``hidden_size /
dim_model_base`` before the head tied to the embedding.  The departures
named in the configuration file hold here too: plain RoPE (by halves)
where the source applies LongRoPE factors, and the program's RMSNorm
epsilon.

It imports nothing of the program.  The weights are made here, from the
run's seed, in the layout the program reads: matrices stored as
``x @ W``, layers stacked on a leading axis, each RMSNorm weight kept as
``scale`` with the norm multiplying by ``1 + scale``, the query's
heads as ``[no-rope | rope]`` and the key's and value's up-projections
as separate matrices.

``compare`` runs the whole sequence (document, question and served
tokens) layer by layer, without a cache, at ``HIGHEST`` matmul
precision, with attention computed in blocks of queries under a causal
mask so that a sequence of 16.9k positions fits beside the weights.  It
returns, for every served token, how far its logit lies below the
reference's best logit at that position, and how far the program's
logits lie from the reference's where the run kept them.  With
``control=True`` the reference is put in the program's place at the
precision below the configuration's bfloat16: every weight matrix and
its input rounded to fp8 (e4m3, scaled per output channel and per
token), with the residual stream and attention in bfloat16 as the
program keeps them, and the same two numbers are read for the control's
own first-ranked tokens and logits.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

HIGHEST = jax.lax.Precision.HIGHEST
# the program's RMSNorm epsilon (departures.rms_norm_eps)
EPS = 1e-6
# queries per attention block: a block's scores over 16.9k keys and 40
# heads are 0.7 GB in float32
QUERY_BLOCK = 256


def _dims(cfg: dict) -> dict:
    return dict(
        L=cfg["num_hidden_layers"], d=cfg["hidden_size"],
        H=cfg["num_attention_heads"], qr=cfg["q_lora_rank"],
        kvr=cfg["kv_lora_rank"], nope=cfg["qk_nope_head_dim"],
        rope=cfg["qk_rope_head_dim"], vd=cfg["v_head_dim"],
        ff=cfg["intermediate_size"], V=cfg["vocab_size"])


def residual_scale(cfg: dict) -> float:
    """scale_depth / sqrt(num_hidden_layers) of the published depth."""
    depth = cfg.get("published_num_hidden_layers",
                    cfg["num_hidden_layers"])
    return cfg["scale_depth"] / np.sqrt(depth)


def head_divisor(cfg: dict) -> float:
    return cfg["hidden_size"] / cfg["dim_model_base"]


def init_weights(cfg: dict, key: jax.Array) -> dict:
    """Random weights for ``cfg``: every layer matrix normal(0, 1 /
    sqrt(fan_in)), the token embedding normal(0, 0.001), every norm
    scale 0.1 * normal(0, 1).  Jit it: the whole tree is made on the
    device in one call.

    Each projection so keeps its input's scale, and the residual
    branches, each scaled by scale_depth / sqrt(62), build a hidden
    state of about unit size over the layers.  The embedding enters at
    scale_emb times its own size, 0.012, small beside that, because the
    head is the embedding's transpose: an embedding of the hidden
    state's size would make the input token's own logit the largest,
    and the random model would repeat its last token, which no
    comparison of served tokens could tell from a broken one."""
    k = _dims(cfg)
    L, d, H, qr, kvr = (k[n] for n in ("L", "d", "H", "qr", "kvr"))
    nope, rope, vd, ff, V = (k[n] for n in ("nope", "rope", "vd", "ff", "V"))
    keys = iter(jax.random.split(key, 16))

    def mat(*shape):
        return (jax.random.normal(next(keys), shape, jnp.float32)
                / np.sqrt(shape[-2]))

    def scale(*shape):
        return 0.1 * jax.random.normal(next(keys), shape, jnp.float32)

    return {
        "embed": 0.001 * jax.random.normal(next(keys), (V, d), jnp.float32),
        "final_norm": {"scale": scale(d)},
        "blocks": {
            "attn": {"wq_a": mat(L, d, qr),
                     "q_a_norm": {"scale": scale(L, qr)},
                     "wq_b": mat(L, qr, H * (nope + rope)),
                     "wkv_a": mat(L, d, kvr + rope),
                     "kv_a_norm": {"scale": scale(L, kvr)},
                     "wk_b": mat(L, kvr, H * nope),
                     "wv_b": mat(L, kvr, H * vd),
                     "wo": mat(L, H * vd, d)},
            "ln": {"scale": scale(L, d)},
            "ln2": {"scale": scale(L, d)},
            "mlp": {"w_gate": mat(L, d, ff), "w_up": mat(L, d, ff),
                    "w_down": mat(L, ff, d)},
        },
    }


def _rms(x, scale):
    dt, x = x.dtype, x.astype(jnp.float32)
    var = jnp.mean(x * x, axis=-1, keepdims=True)
    return (x * jax.lax.rsqrt(var + EPS) * (1.0 + scale)).astype(dt)


def _rope(x, theta):
    """x: [n, S, heads, r]; rotate halves by position."""
    s, r = x.shape[1], x.shape[-1]
    inv = 1.0 / theta ** (jnp.arange(0, r, 2, dtype=jnp.float32) / r)
    ang = jnp.arange(s, dtype=jnp.float32)[:, None] * inv[None, :]
    cos = jnp.concatenate([jnp.cos(ang)] * 2, -1)[None, :, None, :]
    sin = jnp.concatenate([jnp.sin(ang)] * 2, -1)[None, :, None, :]
    x1, x2 = jnp.split(x, 2, axis=-1)
    return x * cos + jnp.concatenate([-x2, x1], -1) * sin


def _mm_f32(x, w):
    return jnp.matmul(x, w, precision=HIGHEST)


def _fp8(a, axis):
    """a rounded to float8_e4m3fn on a scale that maps its largest
    magnitude along ``axis`` to the format's largest finite value."""
    s = jnp.max(jnp.abs(a), axis=axis, keepdims=True) / 448.0
    s = jnp.where(s == 0, 1.0, s)
    return (a / s).astype(jnp.float8_e4m3fn).astype(jnp.float32) * s


def _mm_fp8(x, w):
    """x per row and w per output column in fp8 (e4m3), products summed
    in float32."""
    x = _fp8(x.astype(jnp.float32), -1)
    return jnp.matmul(x, _fp8(w, 0), precision=HIGHEST)


def _causal_attention(q, k, v, control):
    """softmax(q k^T / sqrt(D)) v under a causal mask, in blocks of
    ``QUERY_BLOCK`` queries.  q, k: [n, S, H, D]; v: [n, S, H, Dv]."""
    n, s, h, dq = q.shape
    prec = jax.lax.Precision.DEFAULT if control else HIGHEST
    cdt = jnp.bfloat16 if control else jnp.float32
    blk = min(QUERY_BLOCK, s)
    nb = -(-s // blk)
    qb = jnp.pad(q, ((0, 0), (0, nb * blk - s), (0, 0), (0, 0)))
    qb = jnp.moveaxis(qb.reshape(n, nb, blk, h, dq), 1, 0)
    keys = jnp.arange(s)

    def block(args):
        i, qi = args
        sc = jnp.einsum("nqhd,nkhd->nhqk", qi.astype(cdt), k.astype(cdt),
                        precision=prec, preferred_element_type=jnp.float32)
        sc = sc / np.sqrt(dq)
        causal = keys[None, :] <= (i * blk + jnp.arange(blk))[:, None]
        p = jax.nn.softmax(jnp.where(causal[None, None], sc, -jnp.inf), -1)
        return jnp.einsum("nhqk,nkhd->nqhd", p.astype(cdt), v.astype(cdt),
                          precision=prec, preferred_element_type=jnp.float32)

    out = jax.lax.map(block, (jnp.arange(nb), qb))   # [nb, n, blk, H, Dv]
    return jnp.moveaxis(out, 0, 1).reshape(n, nb * blk, h, -1)[:, :s]


@functools.partial(jax.jit, static_argnames=("cfg_items", "control"))
def _layer(h, blocks, i, cfg_items, control):
    cfg = dict(cfg_items)
    k = _dims(cfg)
    theta = float(cfg["rope_theta"])
    mm = _mm_fp8 if control else _mm_f32
    res = residual_scale(cfg)
    lw = jax.tree.map(lambda a: jax.lax.dynamic_index_in_dim(
        a, i, keepdims=False), blocks)
    n, s, _ = h.shape
    H, kvr, nope, rope, vd = (k[x] for x in ("H", "kvr", "nope", "rope", "vd"))
    a = lw["attn"]
    x = _rms(h, lw["ln"]["scale"])
    qa = _rms(mm(x, a["wq_a"]), a["q_a_norm"]["scale"])
    q = mm(qa, a["wq_b"]).reshape(n, s, H, nope + rope)
    q = jnp.concatenate([q[..., :nope], _rope(q[..., nope:], theta)], -1)
    kv = mm(x, a["wkv_a"])
    c = _rms(kv[..., :kvr], a["kv_a_norm"]["scale"])
    k_rope = _rope(kv[..., kvr:][:, :, None, :], theta)
    k_nope = mm(c, a["wk_b"]).reshape(n, s, H, nope)
    key = jnp.concatenate(
        [k_nope, jnp.broadcast_to(k_rope, (n, s, H, rope)).astype(
            k_nope.dtype)], -1)
    v = mm(c, a["wv_b"]).reshape(n, s, H, vd)
    o = _causal_attention(q, key, v, control).astype(h.dtype)
    h = (h + res * mm(o.reshape(n, s, H * vd), a["wo"])).astype(h.dtype)
    x = _rms(h, lw["ln2"]["scale"])
    m = lw["mlp"]
    up = jax.nn.silu(mm(x, m["w_gate"])) * mm(x, m["w_up"])
    return (h + res * mm(up, m["w_down"])).astype(h.dtype)


@functools.partial(jax.jit, static_argnames=("cfg_items",))
def _embed(embed, tokens, cfg_items):
    return jnp.take(embed, tokens, axis=0) * np.float32(
        dict(cfg_items)["scale_emb"])


@functools.partial(jax.jit, static_argnames=("cfg_items", "control"))
def _head(h, w, pos, cfg_items, control):
    mm = _mm_fp8 if control else _mm_f32
    x = _rms(jnp.take_along_axis(h, pos[..., None], axis=1),
             w["final_norm"]["scale"])
    x = x / np.float32(head_divisor(dict(cfg_items)))
    return mm(x, w["embed"].T).astype(jnp.float32)     # [n, G, V]


def logits_at(weights: dict, cfg: dict, tokens: jax.Array,
              positions: jax.Array, control: bool = False) -> jax.Array:
    """Logits [n, G, V] at ``positions`` [n, G] of sequences ``tokens``
    [n, S], computed layer by layer over the whole sequence."""
    items = tuple(sorted((k, v) for k, v in cfg.items()
                         if isinstance(v, (int, float)) and v is not None))
    h = _embed(weights["embed"], tokens, items)
    if control:
        h = h.astype(jnp.bfloat16)
    for i in range(cfg["num_hidden_layers"]):
        h = _layer(h, weights["blocks"], i, items, control)
    return _head(h, weights, positions, items, control)


def compare(weights: dict, cfg: dict, prompts: np.ndarray,
            served: np.ndarray, kept: list[int], program_logits=None,
            control: bool = False, block: int = 1) -> dict[str, np.ndarray]:
    """What a served answer is checked by, against the float32
    reference run over its prompt (document and question) and served
    tokens.

    prompts: [n, P] ids; served: [n, G] ids the program returned, the
    first from the step fed the question; program_logits: [n, len(kept)]
    rows of the program's logits for served tokens ``kept`` (token t
    comes from the logits at position P - 1 + t).  Returns

      gap      [n, G]: the reference's best logit less the logit of each
               served token, at the position that produced it;
      rel_err  [n]: relative L2 distance of the program's kept logits
               from the reference's.

    With ``control`` the fp8 reference takes the program's place: its
    first-ranked token and its logits are read instead.  Runs ``block``
    sequences at a time."""
    n, p = prompts.shape
    g = served.shape[1]
    seq = np.concatenate([prompts, served[:, :-1]], axis=1).astype(np.int32)
    pos = np.broadcast_to(np.arange(p - 1, p - 1 + g), (n, g))
    kept = np.asarray(kept)
    gaps, errs = [], []
    for lo in range(0, n, block):
        sl = slice(lo, lo + block)
        toks, at = jnp.asarray(seq[sl]), jnp.asarray(pos[sl])
        ref = logits_at(weights, cfg, toks, at)
        if control:
            low = logits_at(weights, cfg, toks, at, True)
            pick, got = jnp.argmax(low, -1), low[:, kept]
            del low
        else:
            pick = jnp.asarray(served[sl])
            got = jnp.asarray(program_logits[sl], jnp.float32)
        chosen = jnp.take_along_axis(ref, pick[..., None], -1)[..., 0]
        gaps.append(np.asarray(jnp.max(ref, -1) - chosen))
        want = ref[:, kept]
        errs.append(np.asarray(
            jnp.linalg.norm((got - want).reshape(got.shape[0], -1), axis=-1)
            / jnp.linalg.norm(want.reshape(got.shape[0], -1), axis=-1)))
        del ref, want, got
    return {"gap": np.concatenate(gaps), "rel_err": np.concatenate(errs)}
