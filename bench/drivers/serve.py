"""Driver for a served language model: closed-loop batches through the
program's jitted prefill and decode steps.

The steps are built and called as ``repro.launch.serve.main`` builds and
calls them: ``make_prefill_step`` and ``make_decode_step`` under
``jax.jit`` with no donation, the first token taken by ``argmax`` over
the prefill's last logits, and every step's greedy token read back to
the host, as a streaming server reads it.

Traffic (``bench/traffic/<mix>.json``, kind ``closed_batches``): batches
of ``batch`` prompts of ``prompt_len`` random ids, each request served
``gen_len`` tokens; the next batch is handed over when the last one's
final token is on the host.  Prompts come from ``(seed, batch index)``.
"""
from __future__ import annotations

import dataclasses
import time

import jax
import jax.numpy as jnp
import numpy as np

from bench.stats import percentile
from repro.configs.base import ArchConfig, RuntimeConfig
from repro.launch.steps import make_decode_step, make_prefill_step
from repro.models import DTypePolicy


def arch_from(shapes: dict) -> ArchConfig:
    """The program's configuration of a Qwen3-style dense decoder from
    its Hugging Face ``config.json`` keys."""
    return ArchConfig(
        name=shapes.get("model_type", "dense"), family="dense",
        n_layers=shapes["num_hidden_layers"], d_model=shapes["hidden_size"],
        n_heads=shapes["num_attention_heads"],
        n_kv_heads=shapes["num_key_value_heads"],
        d_ff=shapes["intermediate_size"], vocab=shapes["vocab_size"],
        head_dim=shapes["head_dim"], qk_norm=True, act=shapes["hidden_act"],
        gated_mlp=True, tie_embeddings=shapes["tie_word_embeddings"],
        rope_theta=float(shapes["rope_theta"]))


def jax_key(seed: int, stream: int) -> jax.Array:
    """A JAX key from a seed of any size (``jax.random.key`` keeps only
    its low 32 bits)."""
    return jax.random.key(int(np.random.default_rng([seed, stream])
                              .integers(0, 2**31)))


@dataclasses.dataclass
class Batch:
    index: int
    prompts: np.ndarray            # [B, P]
    submit: float                  # host time the batch was handed over
    token_times: list[float]       # host time each step's ids arrived
    tokens: list[np.ndarray]       # each [B, 1]
    logits: dict[int, jax.Array]   # token index -> the step's [B, 1, V]

    @property
    def served(self) -> np.ndarray:
        return np.concatenate(self.tokens, axis=1)


class Server:
    """The program's serve steps over weights made from the seed."""

    def __init__(self, config: dict, traffic: dict, seed: int,
                 reference) -> None:
        self.shapes = config["shapes"]
        self.arch = arch_from(self.shapes)
        self.seed = seed
        self.batch = traffic["batch"]
        self.prompt_len = traffic["prompt_len"]
        self.gen_len = traffic["gen_len"]
        init = jax.jit(lambda k: reference.init_weights(self.shapes, k))
        self.params = init(jax_key(seed, 0))
        rt = RuntimeConfig(remat="none")
        policy = DTypePolicy.standard()
        self.prefill = jax.jit(make_prefill_step(
            self.arch, rt, policy, self.prompt_len + self.gen_len))
        self.decode = jax.jit(make_decode_step(self.arch, rt, policy))

    def prompts(self, index: int) -> np.ndarray:
        rng = np.random.default_rng([self.seed, 1, index])
        return rng.integers(0, self.arch.vocab,
                            (self.batch, self.prompt_len), dtype=np.int32)

    def serve(self, index: int, spans, deadline: float = float("inf"),
              n_steps: int | None = None, on_step=None,
              keep_every: int = 0) -> Batch:
        """Serve batch ``index``: prefill, then decode until every
        request has ``gen_len`` tokens, or the host clock passes
        ``deadline``, or ``n_steps`` decode steps ran.  ``on_step(k)``
        is called before decode step ``k`` is handed over.  The logits
        of every ``keep_every``-th token stay referenced for the check
        (no copy, no device work)."""
        with spans.span("gen_prompts"):
            prompts = self.prompts(index)
        submit = time.perf_counter()
        with spans.span("prefill"):
            logits, cache = self.prefill(
                self.params, {"tokens": jnp.asarray(prompts)})
            last = jnp.argmax(logits[:, -1:, :], axis=-1).astype(jnp.int32)
        with spans.span("readback"):
            tokens = [np.asarray(last)]
        times = [time.perf_counter()]
        kept = {0: logits} if keep_every else {}
        steps = self.gen_len - 1 if n_steps is None else n_steps
        for k in range(steps):
            if times[-1] >= deadline:
                break
            if on_step is not None:
                on_step(k)
            with spans.span("decode_step"):
                last, logits, cache = self.decode(self.params, cache, last)
            with spans.span("readback"):
                tokens.append(np.asarray(last))
            times.append(time.perf_counter())
            if keep_every and (k + 1) % keep_every == 0:
                kept[k + 1] = logits
        del cache, logits
        return Batch(index, prompts, submit, times, tokens, kept)


def run(config: dict, traffic: dict, seed: int, seconds: float, *, spans,
        tracer, reference, limits: dict, t_start: float,
        control: bool = False) -> dict:
    """Set up, warm up, serve closed-loop batches for ``seconds``, then
    check a sample of the finished requests against ``reference``.
    With ``control`` the check judges the reference's lower-precision
    control in the program's place (see :func:`check`)."""
    with spans.span("setup.weights"):
        server = Server(config, traffic, seed, reference)
        jax.block_until_ready(server.params)
    # warm-up: the shapes the window uses, one prefill and two decode
    # steps, on batch 0, which the window never serves
    with spans.span("setup.warmup"):
        server.serve(0, spans, n_steps=min(2, server.gen_len - 1))
    setup = {name: spans.total(name)[0]
             for name in ("setup.weights", "setup.warmup")}
    spans.records.clear()

    batches: list[Batch] = []
    contexts: list[int] = []          # live context of each traced step

    def on_step(k: int) -> None:
        if tracer.on:
            contexts.append(server.prompt_len + k + 1)

    t0 = time.perf_counter()
    end = t0 + seconds
    tracer.start()
    while time.perf_counter() < end:
        batches.append(server.serve(
            len(batches) + 1, spans, deadline=end, on_step=on_step,
            keep_every=limits["keep_logits_every"]))
        tracer.unit_done()
    tracer.stop()

    # every token, gap and first token that arrived inside the window
    b_sz = server.batch
    tokens = sum(b_sz * sum(t <= end for t in b.token_times)
                 for b in batches)
    gaps = [t1 - t0_ for b in batches
            for t0_, t1 in zip(b.token_times, b.token_times[1:])
            if t1 <= end] * b_sz
    ttft = [b.token_times[0] - b.submit for b in batches
            if b.token_times[0] <= end] * b_sz
    e2e = {"setup_s": t0 - t_start, "decode_tok_s": tokens / seconds}
    if gaps:
        e2e["itl_ms_p95"] = 1e3 * percentile(gaps, 95)
    if ttft:
        e2e["ttft_ms_p95"] = 1e3 * percentile(ttft, 95)
    finished = [b for b in batches if len(b.tokens) == server.gen_len
                and b.token_times[-1] <= end]
    invalid = sum(int(((b.served < 0) | (b.served >= server.arch.vocab))
                      .any(axis=1).sum()) for b in batches)
    memory = jax.devices()[0].memory_stats() or {}

    for b in batches[len(finished):]:
        b.logits.clear()
    t_check = time.perf_counter()
    checks = check(server, finished, reference, limits, seed, control)
    checks.append({"name": "invalid_requests", "value": invalid,
                   "limit": 0, "ok": invalid == 0})
    return {
        "window": (t0, end), "e2e": e2e, "setup": setup,
        "attempted": b_sz * len(batches), "failed": invalid,
        "checks": checks, "check_s": time.perf_counter() - t_check,
        "memory_peak_bytes": memory.get("peak_bytes_in_use"),
        "counts": {"batches": len(batches), "finished": len(finished),
                   "tokens": tokens, "itl_samples": len(gaps),
                   "ttft_samples": len(ttft)},
        "context": {"batch": b_sz, "prompt_len": server.prompt_len,
                    "decode_contexts": contexts},
    }


def sample(finished: list[Batch], batch: int, want: int, seed: int):
    """``want`` requests of the finished batches, drawn from the seed:
    their prompts, served ids, kept token indices and kept logits."""
    rows = [(b, r) for b in finished for r in range(batch)]
    pick = np.random.default_rng([seed, 2]).choice(
        len(rows), size=min(want, len(rows)), replace=False)
    kept = sorted(finished[0].logits)
    prompts = np.stack([rows[i][0].prompts[rows[i][1]] for i in pick])
    served = np.stack([rows[i][0].served[rows[i][1]] for i in pick])
    logits = jnp.stack([jnp.stack([rows[i][0].logits[t][rows[i][1], 0]
                                   for t in kept]) for i in pick])
    return prompts, served, kept, logits


def judge(got: dict, limits: dict) -> list[dict]:
    """The numbers of ``reference.compare``'s answer, each beside its
    limit from ``limits``: what decides ``correct``."""
    out = []
    for name, key in (("served_logit_gap", "gap"),
                      ("logit_rel_err", "rel_err")):
        value = float(got[key].max())
        limit = limits["numbers"][name]["limit"]
        out.append({"name": name, "value": value, "limit": limit,
                    "ok": value <= limit})
    out.append({"name": "served_tokens_compared",
                "value": int(got["gap"].size), "limit": 1,
                "ok": got["gap"].size >= 1})
    return out


def check(server: Server, finished: list[Batch], reference, limits: dict,
          seed: int, control: bool = False) -> list[dict]:
    """The served tokens and kept logits of a sample of finished
    requests, drawn from the seed, against the reference.  With
    ``control`` the reference's lower-precision control stands in the
    program's place: its first-ranked tokens and its logits at the same
    positions of the same sequences are judged instead."""
    if not finished:
        return [{"name": "finished_requests", "value": 0, "limit": 1,
                 "ok": False}]
    prompts, served, kept, logits = sample(
        finished, server.batch, limits["sample_requests"], seed)
    for b in finished:
        b.logits.clear()
    params, server.params = server.params, None
    return judge(reference.compare(params, server.shapes, prompts, served,
                                   kept, None if control else logits,
                                   control=control), limits)
