"""Plain float32 reference of fault-aware retraining, written for the benchmark.

One member at a time and one sequence at a time, in straightforward
``jax.numpy``, importing nothing of the program:

* the batches follow the noisy-copy token law that the retrained program
  feeds itself (each token the previous one under a fixed permutation of
  the vocabulary, replaced by a random token at the noise rate; the label
  is the next token), rebuilt here from the law's seed and the step;
* the loss is the mean next-token cross-entropy over every position of
  the batch, through the chip's masked weights (``model.masked_weights``:
  a weight on a faulty PE is zero, so it gets no gradient);
* AdamW with a clip of the global gradient norm, as the mix states it;
* the trained weights are shipped with every GEMM weight masked, and
  nothing else: norm scales and the embedding lookup are not on the array.

Every matmul goes through ``dot`` (``model.f32_dot``, or ``model.fp8_dot``
for the control).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from bench.reference import model as ref

GEMM = ("wq", "wk", "wv", "wo", "wg", "wu", "wd")


def token_batch(law: dict, vocab: int, seq_len: int, batch: int, step) -> dict:
    """The batch of training step ``step`` (before the law's offset)."""
    seed = int(law["seed"])
    perm = jnp.asarray(np.random.default_rng(seed).permutation(vocab))
    key = jax.random.fold_in(jax.random.PRNGKey(seed), step + int(law["step_offset"]))
    k1, k2, k3 = jax.random.split(key, 3)
    first = jax.random.randint(k1, (batch, 1), 0, vocab)
    noisy = jax.random.bernoulli(k2, float(law["noise"]), (batch, seq_len))
    noise_tok = jax.random.randint(k3, (batch, seq_len), 0, vocab)

    def next_token(tok, i):
        nxt = jnp.where(noisy[:, i], noise_tok[:, i], perm[tok])
        return nxt, nxt

    _, toks = jax.lax.scan(next_token, first[:, 0], jnp.arange(seq_len))
    tokens = toks.T
    labels = jnp.concatenate([tokens[:, 1:], perm[tokens[:, -1:]]], axis=1)
    return {"tokens": tokens, "labels": labels}


def shipped(params: dict, ok) -> dict:
    """The weights as a retrained chip receives them: every GEMM weight
    masked; the (tied) embedding, whose lookup is no GEMM, and the norm
    scales as trained."""
    if ok is None:
        return params
    lay = params["layers"]
    mask = lambda w: w * ref.fault_mask(w.shape, ok)
    return {
        **params,
        "layers": {
            **lay,
            "attn": {n: mask(v) if n in GEMM else v for n, v in lay["attn"].items()},
            "mlp": {n: mask(v) for n, v in lay["mlp"].items()},
        },
    }


def _row_loss(params, tokens, labels, ok, model, dot):
    """Sum of the next-token cross-entropy over one sequence."""
    logits = ref.forward(ref.masked_weights(params, ok), tokens, model, dot, checkpoint=True)
    lse = jax.nn.logsumexp(logits, axis=-1)
    gold = jnp.take_along_axis(logits, labels[:, None], axis=-1)[:, 0]
    return jnp.sum(lse - gold)


def _adamw(params, grads, m, v, count, opt: dict):
    gnorm = jnp.sqrt(sum(jnp.sum(g * g) for g in jax.tree_util.tree_leaves(grads)))
    clip = opt.get("grad_clip_norm")
    if clip is not None:
        grads = jax.tree_util.tree_map(
            lambda g: g * jnp.minimum(1.0, float(clip) / (gnorm + 1e-9)), grads)
    b1, b2, eps = float(opt["b1"]), float(opt["b2"]), float(opt["eps"])
    lr, wd = float(opt["lr"]), float(opt["weight_decay"])
    m = jax.tree_util.tree_map(lambda m, g: b1 * m + (1 - b1) * g, m, grads)
    v = jax.tree_util.tree_map(lambda v, g: b2 * v + (1 - b2) * g * g, v, grads)

    def step(p, m, v):
        mhat = m / (1 - b1 ** count)
        vhat = v / (1 - b2 ** count)
        return p - lr * (mhat / (jnp.sqrt(vhat) + eps) + wd * p)

    return jax.tree_util.tree_map(step, params, m, v), m, v


class Trainer:
    """Trains one member at a time from ``params0``; compiled once for all
    members (the chip's healthy-PE grid is an argument)."""

    def __init__(self, model: dict, law: dict, opt: dict, seq_len: int, batch: int,
                 dot=ref.f32_dot):
        self.law, self.opt = law, opt
        self.seq_len, self.batch = seq_len, batch
        vocab = int(model["vocab_size"])
        self._batch = jax.jit(lambda s: token_batch(law, vocab, seq_len, batch, s))
        self._grad = jax.jit(jax.value_and_grad(
            lambda p, t, lab, ok: _row_loss(p, t, lab, ok, model, dot)))
        self._update = jax.jit(lambda p, g, m, v, c: _adamw(p, g, m, v, c, opt))

    def train(self, params0: dict, ok, steps: int) -> tuple[dict, dict]:
        """(trained params, readings): each step's loss and the global
        norm of the first step's gradient, and each leaf's first gradient
        norm."""
        params = params0
        zeros = lambda t: jax.tree_util.tree_map(jnp.zeros_like, t)
        m, v = zeros(params), zeros(params)
        ok = jnp.asarray(ok, jnp.float32)
        n = self.batch * self.seq_len
        losses, first = [], None
        for s in range(steps):
            b = self._batch(s)
            loss, grads = 0.0, zeros(params)
            for r in range(self.batch):
                lr_, g = self._grad(params, b["tokens"][r], b["labels"][r], ok)
                loss += float(lr_)
                grads = jax.tree_util.tree_map(jnp.add, grads, g)
            grads = jax.tree_util.tree_map(lambda g: g / n, grads)
            if first is None:
                first = leaf_norms(grads)
            params, m, v = self._update(params, grads, m, v, jnp.float32(s + 1))
            losses.append(loss / n)
        return params, dict(loss=losses, first_grad=first)


def leaf_norms(tree) -> dict:
    """{leaf path: float32 norm}."""
    return {jax.tree_util.keystr(path): float(jnp.sqrt(jnp.sum(jnp.square(leaf.astype(jnp.float32)))))
            for path, leaf in jax.tree_util.tree_leaves_with_path(tree)}
