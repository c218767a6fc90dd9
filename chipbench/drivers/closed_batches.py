"""Closed-loop batch serving through ``ServeEngine.generate``.

One client sends a batch of ``batch`` seeded prompts of ``prompt_len``
tokens, waits for ``new_tokens`` new tokens of each (``generate`` ends in a
host sync), and sends the next.  Every ``greedy_every``-th request, from
the first on, decodes greedily and the rest sample at ``temperature``
(the same mix for every seed); at temperature 0 all are greedy.

After the window, requests drawn from the seed go through the float32
reference teacher-forced on the served tokens:

* ``check_requests`` greedy ones: ``logit_gap`` is the widest gap by which
  a served token's reference logit lies below the reference's best at
  that position;
* ``check_sampled`` sampled ones: ``sample_z`` is the z-score of the
  served tokens' log-likelihood under the reference's distribution at the
  served temperature, p = softmax(z / T): |sum of log p(x) + H(p)| over
  the root of the summed variances of log p.  Tokens drawn from p read
  about 1; tokens served greedily, or with a broken uniform draw, read far
  above it, and so do tokens drawn at another temperature.
"""

from __future__ import annotations

import gc
import math
import time

import jax
import jax.numpy as jnp
import numpy as np

from chipbench import harness, weights

#: Streams of the seed that are not window requests.
WARMUP, SAMPLE, SAMPLE_SAMPLED = 1 << 40, (1 << 40) + 1, (1 << 40) + 2


def prompts(t: dict, vocab: int, seed: int, i: int) -> np.ndarray:
    """The prompts of request ``i`` (every request has the same sizes)."""
    rng = np.random.default_rng([seed, i])
    return rng.integers(0, vocab, (t["batch"], t["prompt_len"]), dtype=np.int32)


def greedy(t: dict, i: int) -> bool:
    return t["temperature"] <= 0 or i % t["greedy_every"] == 0


def served_gap(ref, c: dict, w: dict, tokens, prompt_len: int, ops=None):
    """Widest gap, over the served positions of ``tokens`` (B, P + n), of a
    served token's reference logit below the reference's best.  With
    ``ops`` (a lower precision) the token judged is the one that precision
    puts first instead of the served one (the control)."""
    z = ref.served_logits(w, c, tokens, prompt_len)
    if ops is None:
        picked = tokens[:, prompt_len:]
    else:
        picked = jnp.argmax(ref.served_logits(w, c, tokens, prompt_len, ops), -1)
    best = jnp.max(z, -1)
    got = jnp.take_along_axis(z, picked[..., None], -1)[..., 0]
    return jnp.max(best - got)


def sampled_terms(ref, c: dict, w: dict, tokens, prompt_len: int,
                  temperature: float, ops=None):
    """(sum of log p(x) + H(p), sum of Var_p(log p)) over the served
    positions of ``tokens`` (B, P + n), where p = softmax(z / T) of the
    float32 reference and x the served token.  A token drawn from p adds 0
    to the first sum in expectation.  With ``ops`` (a lower precision) the
    first sum takes, at each position, the expectation of log p(x) + H(p)
    under that precision's distribution instead of the served token (the
    control)."""
    logp = jax.nn.log_softmax(ref.served_logits(w, c, tokens, prompt_len) / temperature, -1)
    p = jnp.exp(logp)
    h = -jnp.sum(p * logp, -1)
    var = jnp.sum(p * jnp.square(logp + h[..., None]), -1)
    if ops is None:
        got = jnp.take_along_axis(logp, tokens[:, prompt_len:, None], -1)[..., 0]
    else:
        q = jax.nn.softmax(ref.served_logits(w, c, tokens, prompt_len, ops) / temperature, -1)
        got = jnp.sum(q * logp, -1)
    return jnp.sum(got + h), jnp.sum(var)


class Driver:
    def __init__(self, cell, seed: int, devices, annotate):
        self.cell, self.seed, self.annotate = cell, seed, annotate
        self.c, self.t = cell.config, cell.traffic
        self.ref = harness.reference_module(self.c)
        self.served, self.sampled = {}, {}

    # -- set-up -------------------------------------------------------------

    def setup(self) -> None:
        from chipbench import system

        self.cfg = system.program_config(self.c)
        w = weights.make(self.ref.layout(self.c), self.seed)
        self.params = system.program_params(self.cfg, self.c, w)
        del w
        self.engine = system.serve_engine(self.cfg, self.params, self.t, self.seed)
        # One request of the window's length compiles every shape the
        # decode loop has (its final concatenate has one piece per step);
        # a short one in the other mode adds what that mode alone uses.
        modes = sorted({greedy(self.t, i) for i in range(self.t["greedy_every"])})
        for k, g in enumerate(modes):
            self.request(prompts(self.t, self.c["vocab_size"], self.seed, WARMUP + k),
                         g, self.t["new_tokens"] if k == 0 else min(2, self.t["new_tokens"]))

    def request(self, p: np.ndarray, is_greedy: bool, n: int) -> np.ndarray:
        self.engine.temperature = 0.0 if is_greedy else self.t["temperature"]
        return self.engine.generate(p, n).tokens

    # -- the window ---------------------------------------------------------

    def window(self, seconds: float) -> harness.Window:
        t, n = self.t, self.t["new_tokens"]
        with self.annotate("window"):
            w = harness.Window(time.perf_counter())
            i = 0
            while time.perf_counter() - w.start < seconds:
                with self.annotate("batch"):
                    p = prompts(t, self.c["vocab_size"], self.seed, i)
                    g = greedy(t, i)
                sent = time.perf_counter()
                with self.annotate("request"):
                    out = self.request(p, g, n)
                w.items.append(harness.Item(sent, time.perf_counter(), t["batch"] * n))
                (self.served if g else self.sampled)[i] = out
                i += 1
        return w

    def release(self) -> None:
        del self.engine, self.params
        gc.collect()

    # -- the comparison -----------------------------------------------------

    def sample(self) -> tuple:
        """The greedy and the sampled requests to check, drawn from the
        seed."""
        def draw(pool, k, stream):
            ids = sorted(pool)
            rng = np.random.default_rng([self.seed, stream])
            return sorted(rng.choice(ids, size=min(k, len(ids)), replace=False).tolist())

        return (draw(self.served, self.t["check_requests"], SAMPLE),
                draw(self.sampled, self.t.get("check_sampled", 0), SAMPLE_SAMPLED))

    def _weights(self):
        return weights.make(self.ref.layout(self.c), self.seed)

    def gaps(self, ids, ops=None) -> list:
        """``served_gap`` of each greedy request in ``ids``, with weights
        made again from the seed."""
        c, p = self.c, self.t["prompt_len"]
        w = self._weights()
        fn = jax.jit(lambda w, toks: served_gap(self.ref, c, w, toks, p, ops))
        return [float(fn(w, jnp.asarray(self.served[i]))) for i in ids]

    def sample_z(self, ids, tokens=None, ops=None) -> float:
        """``sample_z`` over the sampled requests ``ids`` (their tokens from
        ``tokens``, by default those served in the window)."""
        c, p, temp = self.c, self.t["prompt_len"], self.t["temperature"]
        tokens = self.sampled if tokens is None else tokens
        w = self._weights()
        fn = jax.jit(lambda w, toks: sampled_terms(self.ref, c, w, toks, p, temp, ops))
        terms = [fn(w, jnp.asarray(tokens[i])) for i in ids]
        s, var = (sum(float(t[k]) for t in terms) for k in (0, 1))
        return abs(s) / math.sqrt(var) if var > 0 else float("inf")

    def check(self) -> list:
        ids, sids = self.sample()
        out = []
        if ids:
            out.append(harness.check(self.cell, "logit_gap", max(self.gaps(ids))))
        if sids:
            out.append(harness.check(self.cell, "sample_z", self.sample_z(sids)))
        return out
