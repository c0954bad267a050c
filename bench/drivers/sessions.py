"""Driver for a served latent-attention (MLA) model whose sessions each
hold a long document in the decode cache and are asked question after
question of it.

The steps are built and called as ``repro.launch.serve.main`` builds and
calls them: ``make_prefill_step`` and ``make_decode_step`` under
``jax.jit`` with no donation, and every step's greedy token read back to
the host, as a streaming server reads it.

Traffic (``bench/traffic/<mix>.json``, kind ``document_sessions``):
``sessions`` sessions, each with a document of ``document_len`` random
ids from the seed.  In set-up, each document is prefilled through the
program's prefill at batch 1 into a cache of ``document_len +
answer_len`` positions, and the sessions' caches are put into one batch
cache.  Then, round after round until the window ends, every session is
asked a one-token question, drawn from ``(seed, round, session)``, at
position ``document_len`` and answered greedily with ``answer_len``
tokens; the next round sets the cache's length back to the document's
end (``len`` is shared by the batch), so each document is asked again.
"""
from __future__ import annotations

import dataclasses
import math
import time

import jax
import jax.numpy as jnp
import numpy as np

# judge: bench/control.py judges a seed's readings with the driver's own
from bench.drivers.serve import jax_key, judge  # noqa: F401
from bench.stats import percentile
from repro.configs.base import ArchConfig, RuntimeConfig
from repro.launch.steps import make_decode_step, make_prefill_step
from repro.models import DTypePolicy


def arch_from(config: dict) -> ArchConfig:
    """The program's configuration of a MiniCPM3-style MLA decoder from
    its Hugging Face ``config.json`` keys.  The residual branches take
    the published depth's scale where the file holds fewer layers."""
    s = config["shapes"]
    if s["v_head_dim"] != s["qk_nope_head_dim"]:
        raise ValueError("the program's MLA has one head size for the "
                         "no-rope key and the value")
    depth = s.get("published_num_hidden_layers", s["num_hidden_layers"])
    return ArchConfig(
        name=config["name"], family="dense",
        n_layers=s["num_hidden_layers"], d_model=s["hidden_size"],
        n_heads=s["num_attention_heads"],
        n_kv_heads=s["num_key_value_heads"], d_ff=s["intermediate_size"],
        vocab=s["vocab_size"], head_dim=s["qk_nope_head_dim"],
        attn_type="mla", q_lora_rank=s["q_lora_rank"],
        kv_lora_rank=s["kv_lora_rank"], rope_head_dim=s["qk_rope_head_dim"],
        act=s["hidden_act"], gated_mlp=True,
        tie_embeddings=s["tie_word_embeddings"],
        rope_theta=float(s["rope_theta"]),
        embed_scale=float(s["scale_emb"]),
        residual_scale=s["scale_depth"] / math.sqrt(depth),
        head_divisor=s["hidden_size"] / s["dim_model_base"])


@dataclasses.dataclass
class Round:
    index: int
    documents: np.ndarray          # [S, D], every session's document
    questions: np.ndarray          # [S, 1]
    submit: float                  # host time the questions were handed over
    token_times: list[float]       # host time each step's ids arrived
    tokens: list[np.ndarray]       # each [S, 1]
    logits: dict[int, jax.Array]   # answer token index -> its [S, 1, V]

    @property
    def served(self) -> np.ndarray:
        return np.concatenate(self.tokens, axis=1)


class Server:
    """The program's serve steps over weights made from the seed, and
    the sessions' batch cache."""

    def __init__(self, config: dict, traffic: dict, seed: int,
                 reference) -> None:
        self.shapes = config["shapes"]
        self.arch = arch_from(config)
        self.seed = seed
        self.batch = traffic["sessions"]
        self.document_len = traffic["document_len"]
        self.answer_len = traffic["answer_len"]
        init = jax.jit(lambda k: reference.init_weights(self.shapes, k))
        self.params = init(jax_key(seed, 0))
        rt = RuntimeConfig(remat="none")
        policy = DTypePolicy.standard()
        self.prefill = jax.jit(make_prefill_step(
            self.arch, rt, policy, self.document_len + self.answer_len))
        self.decode = jax.jit(make_decode_step(self.arch, rt, policy))
        self.documents = np.random.default_rng([seed, 1]).integers(
            0, self.arch.vocab, (self.batch, self.document_len),
            dtype=np.int32)
        # the cache length every round starts from, kept on the device
        self.rewind = jax.device_put(np.int32(self.document_len))
        self.cache = None

    def questions(self, index: int) -> np.ndarray:
        rng = np.random.default_rng([self.seed, 3, index])
        return rng.integers(0, self.arch.vocab, (self.batch, 1),
                            dtype=np.int32)

    def open_sessions(self, spans) -> None:
        """Prefill every session's document at batch 1 and put the
        caches into one batch cache.  Each session's cache goes to the
        host as soon as it is made, so that the device holds one
        prefill's work at a time beside the weights."""
        parts = []
        for doc in self.documents:
            with spans.span("prefill"):
                _, cache = self.prefill(self.params,
                                        {"tokens": jnp.asarray(doc[None])})
                parts.append(jax.device_get(cache))
        del cache
        with spans.span("gather"):
            self.cache = {k: jnp.asarray(np.concatenate(
                [p[k] for p in parts], axis=1)) for k in parts[0]
                if k != "len"}
            self.cache["len"] = self.rewind
            jax.block_until_ready(self.cache)

    def serve(self, index: int, spans, deadline: float = float("inf"),
              n_steps: int | None = None, on_step=None,
              keep_every: int = 0) -> Round:
        """Round ``index``: every session's question, then decode until
        every answer has ``answer_len`` tokens, or the host clock passes
        ``deadline``, or ``n_steps`` decode steps ran.  ``on_step(k)``
        is called before decode step ``k`` is handed over.  The logits
        of every ``keep_every``-th answer token stay referenced for the
        check (no copy, no device work)."""
        if self.cache is None:
            self.open_sessions(spans)
        with spans.span("gen_questions"):
            questions = self.questions(index)
        # the steps hold the only reference to the cache: no donation,
        # so a step's input and output caches are both live while it runs
        cache, self.cache = {**self.cache, "len": self.rewind}, None
        submit = time.perf_counter()
        last = jnp.asarray(questions)
        tokens, times, kept = [], [], {}
        for k in range(self.answer_len if n_steps is None else n_steps):
            if times and times[-1] >= deadline:
                break
            if on_step is not None:
                on_step(k)
            with spans.span("decode_step"):
                last, logits, cache = self.decode(self.params, cache, last)
            with spans.span("readback"):
                tokens.append(np.asarray(last))
            times.append(time.perf_counter())
            if keep_every and k % keep_every == 0:
                kept[k] = logits
        self.cache = cache
        return Round(index, self.documents, questions, submit, times, tokens,
                     kept)


def run(config: dict, traffic: dict, seed: int, seconds: float, *, spans,
        tracer, reference, limits: dict, t_start: float,
        control: bool = False) -> dict:
    """Set up (weights, the documents' prefill, a warm-up), ask rounds
    of questions for ``seconds``, then check a sample of the finished
    answers against ``reference``.  With ``control`` the check judges
    the reference's lower-precision control in the program's place (see
    :func:`check`)."""
    with spans.span("setup.weights"):
        server = Server(config, traffic, seed, reference)
        jax.block_until_ready(server.params)
    with spans.span("setup.prefill"):
        server.open_sessions(spans)
    # warm-up: two decode steps of round 0, which the window never asks,
    # the first from the gathered cache and the second from a step's own
    with spans.span("setup.warmup"):
        server.serve(0, spans, n_steps=2)
    setup = {name: spans.total(name)[0]
             for name in ("setup.weights", "setup.prefill", "setup.warmup")}
    spans.records.clear()

    rounds: list[Round] = []
    contexts: list[int] = []          # live context of each traced step

    def on_step(k: int) -> None:
        if tracer.on:
            contexts.append(server.document_len + k + 1)

    t0 = time.perf_counter()
    end = t0 + seconds
    tracer.start()
    while time.perf_counter() < end:
        rounds.append(server.serve(
            len(rounds) + 1, spans, deadline=end, on_step=on_step,
            keep_every=limits["keep_logits_every"]))
        tracer.unit_done()
    tracer.stop()

    # every token and gap that arrived inside the window
    b_sz = server.batch
    tokens = sum(b_sz * sum(t <= end for t in r.token_times) for r in rounds)
    gaps = [t1 - t0_ for r in rounds
            for t0_, t1 in zip(r.token_times, r.token_times[1:])
            if t1 <= end] * b_sz
    e2e = {"setup_s": t0 - t_start, "decode_tok_s": tokens / seconds}
    if gaps:
        e2e["itl_ms_p95"] = 1e3 * percentile(gaps, 95)
    finished = [r for r in rounds if len(r.tokens) == server.answer_len
                and r.token_times[-1] <= end]
    invalid = sum(int(((r.served < 0) | (r.served >= server.arch.vocab))
                      .any(axis=1).sum()) for r in rounds)
    memory = jax.devices()[0].memory_stats() or {}

    for r in rounds[len(finished):]:
        r.logits.clear()
    t_check = time.perf_counter()
    checks = check(server, finished, reference, limits, seed, control)
    checks.append({"name": "invalid_requests", "value": invalid,
                   "limit": 0, "ok": invalid == 0})
    return {
        "window": (t0, end), "e2e": e2e, "setup": setup,
        "attempted": b_sz * len(rounds), "failed": invalid,
        "checks": checks, "check_s": time.perf_counter() - t_check,
        "memory_peak_bytes": memory.get("peak_bytes_in_use"),
        "counts": {"rounds": len(rounds), "finished": len(finished),
                   "tokens": tokens, "itl_samples": len(gaps)},
        "context": {"batch": b_sz, "document_len": server.document_len,
                    "decode_contexts": contexts},
    }


def sample(finished: list[Round], batch: int, want: int, seed: int):
    """``want`` answers of the last finished round, its sessions drawn
    from the seed: their prompts (document and question), served ids,
    kept token indices and kept logits.  The last round is the one a
    wrong rewind of the cache would show in, whenever more than one
    finished."""
    last = finished[-1]
    rows = np.sort(np.random.default_rng([seed, 2]).choice(
        batch, size=min(want, batch), replace=False))
    kept = sorted(last.logits)
    prompts = np.concatenate([last.documents[rows], last.questions[rows]],
                             axis=1)
    logits = jnp.stack([jnp.stack([last.logits[t][r, 0] for t in kept])
                        for r in rows])
    return prompts, last.served[rows], kept, logits


def check(server: Server, finished: list[Round], reference, limits: dict,
          seed: int, control: bool = False) -> list[dict]:
    """The served tokens and kept logits of a sample of finished
    answers, drawn from the seed, against the reference over document,
    question and answer.  With ``control`` the reference's
    lower-precision control stands in the program's place: its
    first-ranked tokens and its logits at the same positions of the same
    sequences are judged instead."""
    if not finished:
        return [{"name": "finished_answers", "value": 0, "limit": 1,
                 "ok": False}]
    prompts, served, kept, logits = sample(
        finished, server.batch, limits["sample_requests"], seed)
    for r in finished:
        r.logits.clear()
    # the reference needs the memory of the cache
    params, server.params, server.cache = server.params, None, None
    return judge(reference.compare(params, server.shapes, prompts, served,
                                   kept, None if control else logits,
                                   control=control), limits)
