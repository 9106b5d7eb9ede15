"""Word2Vec / ParagraphVectors.

Rebuild of upstream ``org.deeplearning4j.models.word2vec.Word2Vec`` and
``ParagraphVectors``. The reference runs skip-gram/CBOW inner loops as native
nd4j ops (``SkipGram``/``CBOW`` custom ops); here the whole minibatch update
— embedding gathers, negative-sampling logits, gradients, scatter-update —
is ONE jitted program with donated embedding tables. Pair generation
(windowing, subsampling, negative draws) stays on host numpy, overlapped
with device steps.

Training objective: skip-gram (or CBOW) with negative sampling:
  L = -log σ(u_ctx · v_in) - Σ_k log σ(-u_negk · v_in)
"""

from __future__ import annotations

import functools
from typing import Iterable, List, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from deeplearning4j_tpu.nlp.tokenization import DefaultTokenizerFactory, TokenizerFactory
from deeplearning4j_tpu.nlp.vocab import VocabCache


def _ns_step_impl(emb_in, emb_out, center, context, negatives, lr, cbow=False):
    """One negative-sampling SGD minibatch.

    emb_in:  (V, D) input vectors   emb_out: (V, D) output vectors
    center:  (B,) int32 — skip-gram: input word; CBOW: target word
    context: (B, C) int32 — skip-gram: C=1 context; CBOW: window words
    negatives: (B, K) int32
    """
    if cbow:
        v = jnp.mean(jnp.take(emb_in, context, axis=0), axis=1)  # (B, D)
        tgt = center
    else:
        v = jnp.take(emb_in, center, axis=0)
        tgt = context[:, 0]
    u_pos = jnp.take(emb_out, tgt, axis=0)  # (B, D)
    u_neg = jnp.take(emb_out, negatives, axis=0)  # (B, K, D)

    pos_logit = jnp.sum(v * u_pos, axis=-1)
    neg_logit = jnp.einsum("bd,bkd->bk", v, u_neg)
    # gradients of -logσ(pos) - Σ logσ(-neg)
    g_pos = jax.nn.sigmoid(pos_logit) - 1.0            # (B,)
    g_neg = jax.nn.sigmoid(neg_logit)                   # (B, K)
    grad_v = g_pos[:, None] * u_pos + jnp.einsum("bk,bkd->bd", g_neg, u_neg)
    grad_u_pos = g_pos[:, None] * v
    grad_u_neg = g_neg[..., None] * v[:, None, :]

    loss = jnp.mean(-jax.nn.log_sigmoid(pos_logit)
                    - jnp.sum(jax.nn.log_sigmoid(-neg_logit), axis=-1))

    def mean_scatter(table, idx, grads):
        """Per-row MEAN of duplicate-index gradients. The sequential
        reference updates each occurrence against fresh values, which is
        self-limiting; a summed scatter multiplies the step of frequent
        words by their batch count and diverges."""
        V = table.shape[0]
        counts = jnp.zeros((V,), grads.dtype).at[idx].add(1.0)
        acc = jnp.zeros_like(table).at[idx].add(grads)
        return table - lr * acc / jnp.maximum(counts, 1.0)[:, None]

    emb_out = mean_scatter(emb_out, tgt, grad_u_pos)
    emb_out = mean_scatter(emb_out, negatives.reshape(-1),
                           grad_u_neg.reshape(-1, grad_u_neg.shape[-1]))
    if cbow:
        c = context.shape[1]
        emb_in = mean_scatter(emb_in, context.reshape(-1),
                              jnp.repeat(grad_v / c, c, axis=0))
    else:
        emb_in = mean_scatter(emb_in, center, grad_v)
    return emb_in, emb_out, loss


_ns_step = functools.partial(jax.jit, donate_argnums=(0, 1),
                             static_argnames=("cbow",))(_ns_step_impl)


@functools.partial(jax.jit, donate_argnums=(0, 1), static_argnames=("cbow",))
def _ns_step_group(emb_in, emb_out, centers, contexts, negatives, lr,
                   cbow=False):
    """G sequential minibatches as ONE device dispatch (lax.fori_loop over
    the stacked leading axis) — table math identical to calling
    ``_ns_step`` G times, minus G-1 host dispatches — the same mechanism
    as ``Environment.dispatch_unroll`` in the nn fit loops; its benefit on
    this machine is not measured. Inputs are
    (G, B)/(G, B, C)/(G, B, K); returns the last step's loss."""
    def body(i, carry):
        ei, eo, _ = carry
        return _ns_step_impl(ei, eo, centers[i], contexts[i], negatives[i],
                             lr, cbow=cbow)
    return jax.lax.fori_loop(
        0, centers.shape[0], body,
        (emb_in, emb_out, jnp.float32(0.0)))


class Word2Vec:
    """Builder mirrors the reference::

        w2v = (Word2Vec.builder()
               .layer_size(100).window_size(5).min_word_frequency(5)
               .negative(5).iterations(1).epochs(1).seed(42)
               .learning_rate(0.025).elements_learning_algorithm("skipgram")
               .build())
        w2v.fit(sentences)          # iterable of strings
        w2v.get_word_vector("day"); w2v.words_nearest("day", 5)
    """

    def __init__(self, layer_size=100, window_size=5, min_word_frequency=5,
                 negative=5, epochs=1, iterations=1, batch_size=512,
                 learning_rate=0.025, min_learning_rate=1e-4, seed=42,
                 subsample=1e-3, algorithm="skipgram",
                 tokenizer_factory: Optional[TokenizerFactory] = None):
        self.layer_size = layer_size
        self.window_size = window_size
        self.min_word_frequency = min_word_frequency
        self.negative = negative
        self.epochs = epochs
        self.iterations = iterations
        self.batch_size = batch_size
        self.learning_rate = learning_rate
        self.min_learning_rate = min_learning_rate
        self.seed = seed
        self.subsample = subsample
        self.algorithm = algorithm
        self.tokenizer_factory = tokenizer_factory or DefaultTokenizerFactory()
        self.vocab: Optional[VocabCache] = None
        self.emb_in: Optional[jax.Array] = None
        self.emb_out: Optional[jax.Array] = None

    # -- builder --
    class Builder:
        def __init__(self):
            self._kw = {}

        def __getattr__(self, key):
            def setter(value):
                self._kw[{"elements_learning_algorithm": "algorithm"}.get(key, key)] = value
                return self
            return setter

        def build(self) -> "Word2Vec":
            return Word2Vec(**self._kw)

    @staticmethod
    def builder() -> "Word2Vec.Builder":
        return Word2Vec.Builder()

    # -- training --
    def _sentences_tokens(self, sentences: Iterable[str]) -> List[List[str]]:
        return [self.tokenizer_factory.create(s).get_tokens() for s in sentences]

    def fit(self, sentences: Iterable[str]) -> "Word2Vec":
        token_lists = self._sentences_tokens(sentences)
        self.vocab = VocabCache(self.min_word_frequency).fit(token_lists)
        V, D = len(self.vocab), self.layer_size
        rng = np.random.default_rng(self.seed)
        self.emb_in = jnp.asarray(
            rng.uniform(-0.5 / D, 0.5 / D, (V, D)).astype(np.float32))
        self.emb_out = jnp.asarray(np.zeros((V, D), np.float32))
        probs = self.vocab.negative_sampling_probs()
        encoded = [self.vocab.encode(t) for t in token_lists]
        cbow = self.algorithm.lower() == "cbow"
        total_steps = max(1, self.epochs * self.iterations)
        from deeplearning4j_tpu.runtime.environment import get_environment
        from deeplearning4j_tpu.runtime.state_packing import GroupedDispatch
        unroll = max(1, get_environment().dispatch_unroll)
        lr_box = [jnp.float32(self.learning_rate)]

        def run_single(a):
            c_, x_, n_ = a
            self.emb_in, self.emb_out, loss = _ns_step(
                self.emb_in, self.emb_out, jnp.asarray(c_), jnp.asarray(x_),
                jnp.asarray(n_), lr_box[0], cbow=cbow)
            return loss

        def run_group(todo):
            # consecutive same-shape batches as ONE dispatch
            # (env.dispatch_unroll, same protocol as the nn fit loops;
            # GroupedDispatch runs partial tails singly so only ONE
            # grouped shape ever compiles)
            self.emb_in, self.emb_out, loss = _ns_step_group(
                self.emb_in, self.emb_out,
                jnp.asarray(np.stack([b[0] for b in todo])),
                jnp.asarray(np.stack([b[1] for b in todo])),
                jnp.asarray(np.stack([b[2] for b in todo])),
                lr_box[0], cbow=cbow)
            return [loss] * len(todo)

        gd = GroupedDispatch(
            unroll=unroll,
            compatible=lambda a, b: a[0].shape == b[0].shape,
            run_single=run_single, run_group=run_group,
            deliver=lambda args, loss: None)
        try:
            for epoch in range(self.epochs):
                lr_box[0] = jnp.float32(max(
                    self.min_learning_rate,
                    self.learning_rate * (1 - epoch / max(1, self.epochs))))
                for _ in range(self.iterations):
                    pairs = self._make_pairs(encoded, rng, cbow)
                    for i in range(0, len(pairs[0]), self.batch_size):
                        sl = slice(i, i + self.batch_size)
                        center, context = pairs[0][sl], pairs[1][sl]
                        negs = rng.choice(
                            len(probs),
                            size=(context.shape[0], self.negative),
                            p=probs).astype(np.int32)
                        gd.submit((center, context, negs))
                    gd.flush()  # epoch boundary: lr changes next epoch
        finally:
            gd.drain_on_error()
        return self

    def _make_pairs(self, encoded: List[List[int]], rng, cbow: bool):
        centers, contexts = [], []
        C = self.window_size
        for sent in encoded:
            n = len(sent)
            for i, w in enumerate(sent):
                win = rng.integers(1, C + 1)
                ctx = [sent[j] for j in range(max(0, i - win), min(n, i + win + 1))
                       if j != i]
                if not ctx:
                    continue
                if cbow:
                    ctx = (ctx * C)[:C]  # pad by repetition to fixed width
                    centers.append(w)
                    contexts.append(ctx)
                else:
                    for c in ctx:
                        centers.append(w)
                        contexts.append([c])
        order = rng.permutation(len(centers))
        return (np.asarray(centers, np.int32)[order],
                np.asarray(contexts, np.int32)[order])

    # -- queries (reference WordVectors API) --
    def get_word_vector(self, word: str) -> Optional[np.ndarray]:
        i = self.vocab.index_of(word)
        return None if i < 0 else np.asarray(self.emb_in[i])

    def has_word(self, word: str) -> bool:
        return self.vocab is not None and self.vocab.contains_word(word)

    def similarity(self, w1: str, w2: str) -> float:
        a, b = self.get_word_vector(w1), self.get_word_vector(w2)
        if a is None or b is None:
            return float("nan")
        return float(a @ b / (np.linalg.norm(a) * np.linalg.norm(b) + 1e-12))

    def words_nearest(self, word: str, n: int = 10) -> List[str]:
        i = self.vocab.index_of(word)
        if i < 0:
            return []
        emb = np.asarray(self.emb_in)
        v = emb[i] / (np.linalg.norm(emb[i]) + 1e-12)
        norms = emb / (np.linalg.norm(emb, axis=1, keepdims=True) + 1e-12)
        sims = norms @ v
        order = np.argsort(-sims)
        return [self.vocab.word_at_index(j) for j in order if j != i][:n]

    def save(self, path: str) -> None:
        from deeplearning4j_tpu.nlp.serializer import WordVectorSerializer
        WordVectorSerializer.write_word_vectors(self, path)


class ParagraphVectors(Word2Vec):
    """PV-DBOW (reference ``ParagraphVectors``): a document vector is trained
    to predict the words it contains (skip-gram with the doc id as input)."""

    def __init__(self, **kw):
        super().__init__(**kw)
        self.doc_vectors: Optional[jax.Array] = None
        self._n_docs = 0

    def fit(self, documents: Iterable[str]) -> "ParagraphVectors":
        token_lists = self._sentences_tokens(documents)
        self.vocab = VocabCache(self.min_word_frequency).fit(token_lists)
        V, D = len(self.vocab), self.layer_size
        self._n_docs = len(token_lists)
        rng = np.random.default_rng(self.seed)
        self.doc_vectors = jnp.asarray(
            rng.uniform(-0.5 / D, 0.5 / D, (self._n_docs, D)).astype(np.float32))
        self.emb_out = jnp.asarray(np.zeros((V, D), np.float32))
        self.emb_in = self.doc_vectors  # alias: docs are the "input words"
        probs = self.vocab.negative_sampling_probs()
        for epoch in range(self.epochs):
            lr = max(self.min_learning_rate,
                     self.learning_rate * (1 - epoch / max(1, self.epochs)))
            centers, contexts = [], []
            for d, toks in enumerate(token_lists):
                for w in self.vocab.encode(toks):
                    centers.append(d)
                    contexts.append([w])
            order = rng.permutation(len(centers))
            centers = np.asarray(centers, np.int32)[order]
            contexts = np.asarray(contexts, np.int32)[order]
            for i in range(0, len(centers), self.batch_size):
                sl = slice(i, i + self.batch_size)
                negs = jnp.asarray(rng.choice(
                    len(probs), size=(len(centers[sl]), self.negative), p=probs)
                    .astype(np.int32))
                self.doc_vectors, self.emb_out, _ = _ns_step(
                    self.doc_vectors, self.emb_out, jnp.asarray(centers[sl]),
                    jnp.asarray(contexts[sl]), negs, jnp.float32(lr), cbow=False)
        self.emb_in = self.doc_vectors
        return self

    def get_doc_vector(self, i: int) -> np.ndarray:
        return np.asarray(self.doc_vectors[i])

    def docs_nearest(self, i: int, n: int = 10) -> List[int]:
        emb = np.asarray(self.doc_vectors)
        v = emb[i] / (np.linalg.norm(emb[i]) + 1e-12)
        sims = (emb / (np.linalg.norm(emb, axis=1, keepdims=True) + 1e-12)) @ v
        return [int(j) for j in np.argsort(-sims) if j != i][:n]
