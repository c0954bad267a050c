"""Plain float32 reference of a Qwen3 dense decoder, and its weights.

Follows the published Qwen3 layer equations (Hugging Face
``modeling_qwen3``): RMSNorm before attention and MLP, grouped-query
attention with RMSNorm on each query and key head, rotary embedding by
halves, a SiLU-gated MLP, a final RMSNorm and a head tied to the token
embedding.  One departure, named in the configuration file: the token
embedding is multiplied by sqrt(hidden_size), as the program under test
does.

It imports nothing of the program.  The weights are made here, from the
run's seed, in the layout the program reads: matrices stored as
``x @ W``, layers stacked on a leading axis, and each RMSNorm weight kept
as ``scale`` with the norm multiplying by ``1 + scale``.

``compare`` runs the whole sequence (prompt and served tokens) layer by
layer, without a cache, at ``HIGHEST`` matmul precision, and returns
for every served token how far its logit lies below the reference's best
logit at that position, and how far the program's logits lie from the
reference's where the run kept them.  With ``control=True`` the
reference is put in the program's place at the precision below the
configuration's bfloat16: every weight matrix and its input rounded to
fp8 (e4m3, scaled per output channel and per token), with the residual
stream and attention in bfloat16 as the program keeps them, and the same
two numbers are read for the control's own first-ranked tokens and
logits.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

HIGHEST = jax.lax.Precision.HIGHEST


def _dims(cfg: dict) -> dict[str, int]:
    d = cfg["hidden_size"]
    hq = cfg["num_attention_heads"]
    return dict(L=cfg["num_hidden_layers"], d=d, hq=hq,
                hkv=cfg["num_key_value_heads"],
                hd=cfg.get("head_dim") or d // hq,
                ff=cfg["intermediate_size"], V=cfg["vocab_size"])


def init_weights(cfg: dict, key: jax.Array) -> dict:
    """Random weights for ``cfg``: every layer matrix normal(0,
    initializer_range), the token embedding normal(0, initializer_range
    / sqrt(hidden_size)), every norm scale 0.1 * normal(0, 1).  Jit it:
    the whole tree is made on the device in one call.

    The embedding is that small because the program multiplies it by
    sqrt(hidden_size): so scaled, it enters the first layer at
    initializer_range.  At the published scale the tied head would see
    the input token's own embedding dominate the last hidden state, and
    the random model would repeat its last token, which no comparison
    of served tokens could tell from a broken one."""
    k = _dims(cfg)
    L, d, hq, hkv, hd, ff, V = (k[n] for n in
                                ("L", "d", "hq", "hkv", "hd", "ff", "V"))
    std = cfg["initializer_range"]
    keys = iter(jax.random.split(key, 13))

    def mat(*shape, scale=std):
        return scale * jax.random.normal(next(keys), shape, jnp.float32)

    def scale(*shape):
        return 0.1 * jax.random.normal(next(keys), shape, jnp.float32)

    return {
        "embed": mat(V, d, scale=std / np.sqrt(d)),
        "final_norm": {"scale": scale(d)},
        "blocks": {
            "attn": {"wq": mat(L, d, hq * hd), "wk": mat(L, d, hkv * hd),
                     "wv": mat(L, d, hkv * hd), "wo": mat(L, hq * hd, d),
                     "q_norm": {"scale": scale(L, hd)},
                     "k_norm": {"scale": scale(L, hd)}},
            "ln": {"scale": scale(L, d)},
            "ln2": {"scale": scale(L, d)},
            "mlp": {"w_gate": mat(L, d, ff), "w_up": mat(L, d, ff),
                    "w_down": mat(L, ff, d)},
        },
    }


def _rms(x, scale, eps):
    dt, x = x.dtype, x.astype(jnp.float32)
    var = jnp.mean(x * x, axis=-1, keepdims=True)
    return (x * jax.lax.rsqrt(var + eps) * (1.0 + scale)).astype(dt)


def _rope(x, theta):
    """x: [n, S, heads, hd]; rotate halves by position."""
    s, hd = x.shape[1], x.shape[-1]
    inv = 1.0 / theta ** (jnp.arange(0, hd, 2, dtype=jnp.float32) / hd)
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


@functools.partial(jax.jit, static_argnames=("cfg_items", "control"))
def _layer(h, blocks, i, cfg_items, control):
    cfg = dict(cfg_items)
    k = _dims(cfg)
    eps, theta = cfg["rms_norm_eps"], float(cfg["rope_theta"])
    mm = _mm_fp8 if control else _mm_f32
    lw = jax.tree.map(lambda a: jax.lax.dynamic_index_in_dim(
        a, i, keepdims=False), blocks)
    n, s, _ = h.shape
    hq, hkv, hd = k["hq"], k["hkv"], k["hd"]
    a = lw["attn"]
    x = _rms(h, lw["ln"]["scale"], eps)
    q = mm(x, a["wq"]).reshape(n, s, hq, hd)
    kk = mm(x, a["wk"]).reshape(n, s, hkv, hd)
    v = mm(x, a["wv"]).reshape(n, s, hkv, hd)
    q = _rope(_rms(q, a["q_norm"]["scale"], eps), theta)
    kk = _rope(_rms(kk, a["k_norm"]["scale"], eps), theta)
    g = hq // hkv
    kk = jnp.repeat(kk, g, axis=2)
    v = jnp.repeat(v, g, axis=2)
    prec = jax.lax.Precision.DEFAULT if control else HIGHEST
    cdt = jnp.bfloat16 if control else jnp.float32
    sc = jnp.einsum("nqhd,nkhd->nhqk", q.astype(cdt), kk.astype(cdt),
                    precision=prec, preferred_element_type=jnp.float32)
    sc = sc / np.sqrt(hd)
    causal = jnp.tril(jnp.ones((s, s), bool))
    sc = jnp.where(causal[None, None], sc, -jnp.inf)
    p = jax.nn.softmax(sc, axis=-1)
    o = jnp.einsum("nhqk,nkhd->nqhd", p.astype(cdt), v.astype(cdt),
                   precision=prec, preferred_element_type=jnp.float32)
    h = (h + mm(o.reshape(n, s, hq * hd), a["wo"])).astype(h.dtype)
    x = _rms(h, lw["ln2"]["scale"], eps)
    m = lw["mlp"]
    up = jax.nn.silu(mm(x, m["w_gate"])) * mm(x, m["w_up"])
    return (h + mm(up, m["w_down"])).astype(h.dtype)


@functools.partial(jax.jit, static_argnames=("cfg_items",))
def _embed(embed, tokens, cfg_items):
    d = dict(cfg_items)["hidden_size"]
    return jnp.take(embed, tokens, axis=0) * np.float32(np.sqrt(d))


@functools.partial(jax.jit, static_argnames=("cfg_items", "control"))
def _head(h, w, pos, cfg_items, control):
    cfg = dict(cfg_items)
    mm = _mm_fp8 if control else _mm_f32
    x = _rms(jnp.take_along_axis(h, pos[..., None], axis=1),
             w["final_norm"]["scale"], cfg["rms_norm_eps"])
    return mm(x, w["embed"].T)                       # [n, G, V]


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
            control: bool = False, block: int = 4) -> dict[str, np.ndarray]:
    """What a served request is checked by, against the float32
    reference run over its prompt and served tokens.

    prompts: [n, P] ids; served: [n, G] ids the program returned, the
    first from the prefill; program_logits: [n, len(kept)] rows of the
    program's logits for served tokens ``kept`` (token t comes from the
    logits at position P - 1 + t).  Returns

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
