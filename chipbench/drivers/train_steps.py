"""Training steps through the program's jitted, donated train step.

Set-up builds the one state and step that the window will drive, and
drives them through the first ``checked_steps`` steps on seeded rows (all
rows of every step differ).  On the way it reads, from the state itself,
the norm of each weight's first gradient as the optimizer received it
(Adam's first moment after step 1, over 1 - beta1) and, after the last of
those steps, the norm of each weight's change since initialisation.  The
window then runs further steps on the same state, each timed to its host
sync.

After the window the float32 reference trains from the same seeded
weights on the same rows and the comparison takes, per weight and layer:
``loss_gap``, the largest difference of a step's loss; ``grad_gap`` and
``update_gap``, the largest difference of the two norms over the larger of
the reference's norm and the median norm.  Weights whose reference
gradient is under a thousandth of the median's are left out.
"""

from __future__ import annotations

import gc
import time

import jax
import jax.numpy as jnp
import numpy as np

from chipbench import harness, weights
from chipbench.reference import adamw


def rows(t: dict, vocab: int, seed: int, step: int) -> np.ndarray:
    """The token rows of training step ``step``."""
    rng = np.random.default_rng([seed, step])
    return rng.integers(0, vocab, (t["batch"], t["seq_len"]), dtype=np.int32)


def leaf_norms(ref, w: dict) -> dict:
    """Per-weight norms, one per layer for weights with a layer axis."""
    return {k: (jnp.sqrt(jnp.sum(jnp.square(x.astype(jnp.float32)),
                                 axis=tuple(range(1, x.ndim))))
                if ref.stacked(k) else jnp.sqrt(jnp.sum(jnp.square(x.astype(jnp.float32)))))
            for k, x in w.items()}


def change_norms(ref, layout: dict, w: dict, seed: int) -> dict:
    """``leaf_norms`` of ``w`` less the weights the seed made."""
    def norms(w, k):
        w0 = weights.generate(layout, k)
        return leaf_norms(ref, {n: w[n].astype(jnp.float32) - w0[n] for n in w0})

    return jax.device_get(jax.jit(norms)(w, weights.key(seed)))


def leaf_gap(got: dict, want: dict, grad: dict) -> float:
    """max |got - want| / max(want, median want) over weights and layers,
    leaving out those whose reference gradient ``grad`` is under a
    thousandth of the median."""
    flat = lambda d: np.concatenate([np.ravel(np.asarray(d[k], np.float64))
                                     for k in sorted(want)])
    g, w, r = flat(got), flat(want), flat(grad)
    keep = r >= 1e-3 * np.median(r)
    scale = np.maximum(w, np.median(w))
    return float(np.max(np.abs(g - w)[keep] / scale[keep]))


def gaps(got, want) -> dict:
    """The three numbers compared, of readings ``got`` against ``want``
    (each: losses, first-gradient norms, change norms)."""
    return {"loss_gap": max(abs(a - b) for a, b in zip(got[0], want[0])),
            "grad_gap": leaf_gap(got[1], want[1], want[1]),
            "update_gap": leaf_gap(got[2], want[2], want[1])}


class Driver:
    def __init__(self, cell, seed: int, devices, annotate):
        self.cell, self.seed, self.annotate = cell, seed, annotate
        self.c, self.t = cell.config, cell.traffic
        self.ref = harness.reference_module(self.c)
        self.layout = self.ref.layout(self.c)

    def rows(self, step: int) -> np.ndarray:
        return rows(self.t, self.c["vocab_size"], self.seed, step)

    def setup(self) -> None:
        from chipbench import system

        ad = system.adapter(self.c)
        self.cfg = system.program_config(self.c)
        w = weights.make(self.layout, self.seed)
        state = system.train_state(self.cfg, system.program_params(self.cfg, self.c, w))
        del w
        self.step = system.train_step(self.cfg, self.t)
        b1 = self.t["optimizer"]["beta1"]
        first = jax.jit(lambda m: leaf_norms(self.ref, ad.from_program(m)))
        self.losses = []
        for s in range(self.t["checked_steps"]):
            state, m = self.step(state, {"tokens": self.rows(s)})
            self.losses.append(float(m["loss"]))
            if s == 0:
                self.grad = {k: np.asarray(v) / (1 - b1)
                             for k, v in jax.device_get(first(state["opt"]["m"])).items()}
        self.change = change_norms(self.ref, self.layout,
                                   ad.from_program(state["params"]), self.seed)
        self.state, self.next = state, self.t["checked_steps"]

    def window(self, seconds: float) -> harness.Window:
        tokens = self.t["batch"] * self.t["seq_len"]
        with self.annotate("window"):
            w = harness.Window(time.perf_counter())
            while time.perf_counter() - w.start < seconds:
                with self.annotate("batch"):
                    batch = {"tokens": self.rows(self.next)}
                sent = time.perf_counter()
                with self.annotate("train_step"):
                    self.state, m = self.step(self.state, batch)
                    jax.block_until_ready((self.state, m))
                w.items.append(harness.Item(sent, time.perf_counter(), tokens))
                self.next += 1
        return w

    def release(self) -> None:
        del self.state, self.step
        gc.collect()

    def reference(self, ops=None, rows_kept=None):
        """Losses, first-gradient norms and change norms of the reference
        over the checked steps (``ops``: another precision; ``rows_kept``:
        train on only the first rows of each step)."""
        ref, c, o = self.ref, self.c, self.t["optimizer"]
        ops = ops or ref.FP32
        z = self.t["z_loss_weight"]
        batches = [jnp.asarray(self.rows(s)[:rows_kept])
                   for s in range(self.t["checked_steps"])]
        losses, grad, w = adamw.train(
            lambda p, b: ref.loss(p, c, b, z, ops),
            weights.make(self.layout, self.seed), batches, o, ref.decayed,
            lambda g: leaf_norms(ref, g))
        return losses, grad, change_norms(ref, self.layout, w, self.seed)

    def readings(self):
        """This run's losses, first-gradient norms and change norms."""
        return self.losses, self.grad, self.change

    def check(self) -> list:
        got = gaps(self.readings(), self.reference())
        return [harness.check(self.cell, k, v) for k, v in got.items()]
