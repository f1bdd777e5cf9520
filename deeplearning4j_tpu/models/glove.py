"""GloVe: AdaGrad weighted least squares on co-occurrence log-counts.

≙ reference models/glove/Glove.java:42 (fit:91, doIteration:151),
GloveWeightLookupTable (bias vectors + per-row AdaGrad), and
CoOccurrences.java:41 (window-weighted co-occurrence counting, the actor
pipeline replaced by a plain host-side pass).

TPU re-design: co-occurrence triples (i, j, X_ij) are counted host-side
once, then shuffled into fixed-size batches; each epoch's updates run as
jitted scatter-add AdaGrad steps — the batched equivalent of the
reference's per-pair ``iterateSample`` loop.
"""

from __future__ import annotations

import functools
from collections import Counter

import jax
import jax.numpy as jnp
import numpy as np

from deeplearning4j_tpu.nlp.sentence_iterator import SentenceIterator
from deeplearning4j_tpu.nlp.tokenization import DefaultTokenizer
from deeplearning4j_tpu.nlp.vocab import VocabCache


def count_cooccurrences(
    encoded_sentences, window: int = 5
) -> tuple[np.ndarray, np.ndarray, np.ndarray]:
    """Window-weighted counts (weight 1/distance, ≙ CoOccurrences.fit:69).

    Returns (rows, cols, values) for the upper+lower triangle.
    """
    counts: Counter = Counter()
    for ids in encoded_sentences:
        n = len(ids)
        for i in range(n):
            for off in range(1, window + 1):
                j = i + off
                if j < n:
                    counts[(ids[i], ids[j])] += 1.0 / off
                    counts[(ids[j], ids[i])] += 1.0 / off
    if not counts:
        return (np.zeros(0, np.int32),) * 2 + (np.zeros(0, np.float32),)
    keys = np.array(list(counts.keys()), dtype=np.int32)
    vals = np.array(list(counts.values()), dtype=np.float32)
    return keys[:, 0], keys[:, 1], vals


def _glove_math(w, wc, b, bc, hw, hwc, hb, hbc, rows, cols, logx, fx, lr):
    """One batched AdaGrad WLS step (pure math, reused by the sharded path).

    w/wc: word and context embeddings (V, D); b/bc biases (V,);
    h*: AdaGrad accumulators.  loss = f(X) * (w_i.wc_j + b_i + bc_j - logX)^2
    """
    wi = w[rows]
    wj = wc[cols]
    diff = jnp.einsum("bd,bd->b", wi, wj) + b[rows] + bc[cols] - logx
    fdiff = fx * diff  # (B,)
    g_wi = fdiff[:, None] * wj
    g_wj = fdiff[:, None] * wi
    # AdaGrad per-row
    hw = hw.at[rows].add(g_wi**2)
    hwc = hwc.at[cols].add(g_wj**2)
    w = w.at[rows].add(-lr * g_wi / jnp.sqrt(hw[rows] + 1e-8))
    wc = wc.at[cols].add(-lr * g_wj / jnp.sqrt(hwc[cols] + 1e-8))
    hb = hb.at[rows].add(fdiff**2)
    hbc = hbc.at[cols].add(fdiff**2)
    b = b.at[rows].add(-lr * fdiff / jnp.sqrt(hb[rows] + 1e-8))
    bc = bc.at[cols].add(-lr * fdiff / jnp.sqrt(hbc[cols] + 1e-8))
    loss = 0.5 * jnp.mean(fx * diff**2)
    return w, wc, b, bc, hw, hwc, hb, hbc, loss


_glove_step = jax.jit(
    _glove_math, donate_argnums=(0, 1, 2, 3, 4, 5, 6, 7)
)


class Glove:
    """≙ Glove.Builder fields: layer_size, xMax, alpha, lr, epochs."""

    def __init__(
        self,
        layer_size: int = 50,
        window: int = 5,
        min_word_frequency: int = 1,
        lr: float = 0.05,
        x_max: float = 100.0,
        alpha: float = 0.75,
        epochs: int = 5,
        batch: int = 4096,
        seed: int = 123,
        tokenizer=None,
    ):
        self.layer_size = layer_size
        self.window = window
        self.lr = lr
        self.x_max = x_max
        self.alpha = alpha
        self.epochs = epochs
        self.batch = batch
        self.seed = seed
        self.tokenizer = tokenizer or DefaultTokenizer()
        self.cache = VocabCache(min_word_frequency)
        self.w = self.wc = self.b = self.bc = None
        self._acc = None  # AdaGrad history, kept for continue-training
        self.loss_history: list[float] = []

    def _prepare(self, sentences: SentenceIterator):
        """Vocab + co-occurrence counting + weight/accumulator init; returns
        (rows, cols, logx, fx) host arrays and the AdaGrad accumulators."""
        toks = [self.tokenizer.tokens(s) for s in sentences]
        self.cache.fit(toks)
        encoded = [self.cache.encode(t) for t in toks]
        rows, cols, vals = count_cooccurrences(encoded, self.window)
        if len(rows) == 0:
            raise ValueError("empty co-occurrence matrix")
        logx, fx, acc = self._init_weights(vals)
        return rows, cols, logx, fx, acc

    def _init_weights(self, vals: np.ndarray, reset: bool = True):
        """Weight/bias/AdaGrad init + the GloVe weighting terms, shared
        by the sentence and precomputed-co-occurrence fit paths.
        ``reset=False`` keeps already-trained weights (the continue-
        training path) and only rebuilds the per-triple terms."""
        v, d = len(self.cache), self.layer_size
        if reset or self.w is None:
            key = jax.random.key(self.seed)
            k1, k2 = jax.random.split(key)
            self.w = (jax.random.uniform(k1, (v, d)) - 0.5) / d
            self.wc = (jax.random.uniform(k2, (v, d)) - 0.5) / d
            self.b = jnp.zeros((v,))
            self.bc = jnp.zeros((v,))
            self._acc = None
        if self._acc is not None:
            acc = self._acc  # continue-training keeps the AdaGrad history
        else:
            acc = (
                jnp.ones((v, d)), jnp.ones((v, d)),
                jnp.ones((v,)), jnp.ones((v,)),
            )
        logx = np.log(vals).astype(np.float32)
        fx = np.minimum((vals / self.x_max) ** self.alpha, 1.0).astype(
            np.float32
        )
        return logx, fx, acc

    def _run_epochs(self, step, data, acc, bsz: int, reshape=None) -> None:
        """Shared shuffle/batch/loss-history loop over the co-occurrence
        triples; ``reshape`` folds each batch to (n_dev, per) for shard_map."""
        rows, cols, logx, fx = data
        hw, hwc, hb, hbc = acc
        rng = np.random.default_rng(self.seed)
        n = len(rows)
        for _ in range(self.epochs):
            order = rng.permutation(n)
            epoch_loss, nb = 0.0, 0
            for s in range(0, n - bsz + 1, bsz):
                idx = order[s : s + bsz]
                batch = [jnp.asarray(a[idx]) for a in data]
                if reshape is not None:
                    batch = [a.reshape(reshape) for a in batch]
                (self.w, self.wc, self.b, self.bc, hw, hwc, hb, hbc, loss) = step(
                    self.w, self.wc, self.b, self.bc, hw, hwc, hb, hbc,
                    *batch, jnp.float32(self.lr),
                )
                epoch_loss += float(loss)
                nb += 1
            self.loss_history.append(epoch_loss / max(nb, 1))
        # keep the final AdaGrad history so a continue-training call
        # (fit_cooccurrences after fit) steps with the accumulated h,
        # not a fresh near-full-lr restart on already-trained rows
        self._acc = (hw, hwc, hb, hbc)

    def fit(self, sentences: SentenceIterator) -> None:
        rows, cols, logx, fx, acc = self._prepare(sentences)
        bsz = min(self.batch, len(rows))
        self._run_epochs(_glove_step, (rows, cols, logx, fx), acc, bsz)

    def fit_cooccurrences(self, triples) -> None:
        """Train directly on precomputed ``(word_i, word_j, X_ij)``
        triples — the artifact CoOccurrences.fit produces and
        Glove.doIteration consumes in the reference (Glove.java:91,151;
        CoOccurrences.java:69). Lets a real co-occurrence dump (e.g.
        the reference's big/coc.txt fixture) drive the AdaGrad WLS
        optimizer without re-counting.

        Caveats (ADVICE r4):

        - ``min_word_frequency`` here counts how often a word appears
          across the *triples* (each triple contributes one occurrence
          per member), NOT corpus token frequency — the corpus is not
          available in this path, so the cutoff semantics necessarily
          diverge from the reference's CoOccurrences (which prunes on
          corpus counts before counting pairs).
        - if a vocab was already built (``fit()`` ran first), it is
          reused rather than rebuilt, trained weights AND AdaGrad
          history are kept (continue-training), and triples whose words
          are out-of-vocab are dropped. (``fit()`` itself has no such
          guard: VocabCache.fit ACCUMULATES, so calling ``fit()`` twice
          on one model corrupts the word↔index mapping — build the
          vocab once, then continue with this method.)
        """
        triples = [
            (w1, w2, x) for w1, w2, x in
            ((w1, w2, float(x)) for w1, w2, x in triples) if x > 0
        ]
        if not triples:
            raise ValueError("empty co-occurrence input")
        had_vocab = len(self.cache) > 0
        if not had_vocab:
            self.cache.fit([w1, w2] for w1, w2, _ in triples)
        # drop triples whose words the min-frequency cutoff pruned: a -1
        # index would wrap to the last vocab row in the jitted scatter
        # and silently corrupt another word's embedding
        kept = [
            (self.cache.index_of(w1), self.cache.index_of(w2), x)
            for w1, w2, x in triples
        ]
        kept = [(i, j, x) for i, j, x in kept if i >= 0 and j >= 0]
        if not kept:
            raise ValueError("all co-occurrence words pruned by "
                             "min_word_frequency")
        rows = np.asarray([i for i, _, _ in kept], np.int32)
        cols = np.asarray([j for _, j, _ in kept], np.int32)
        vals = np.asarray([x for _, _, x in kept], np.float32)
        logx, fx, acc = self._init_weights(vals, reset=not had_vocab)
        bsz = min(self.batch, len(rows))
        self._run_epochs(_glove_step, (rows, cols, logx, fx), acc, bsz)

    def fit_distributed(self, sentences: SentenceIterator, mesh=None) -> None:
        """Data-parallel GloVe: each device runs the AdaGrad WLS step on its
        shard of every co-occurrence batch from replicated tables, then the
        updated tables (and accumulators) are averaged — the in-graph pmean
        equivalent of the master-side table merge in the reference's
        GloveJobAggregator (scaleout/perform/models/glove/, SURVEY §2-P8)."""
        from jax import shard_map
        from jax.sharding import PartitionSpec as P

        from deeplearning4j_tpu.parallel import mesh as mesh_lib

        mesh = mesh or mesh_lib.data_parallel_mesh()
        n_dev = mesh.devices.size
        axis = mesh_lib.DATA_AXIS

        rows, cols, logx, fx, acc = self._prepare(sentences)
        hw, hwc, hb, hbc = acc

        def per_device(w, wc, b, bc, hw, hwc, hb, hbc, rows, cols, logx, fx, lr):
            out = _glove_math(
                w, wc, b, bc, hw, hwc, hb, hbc,
                rows[0], cols[0], logx[0], fx[0], lr,
            )
            *tables, loss = out
            tables = [jax.lax.pmean(t, axis) for t in tables]
            return (*tables, jax.lax.pmean(loss, axis))

        rep, sh = P(), P(axis)
        step = jax.jit(
            shard_map(
                per_device,
                mesh=mesh,
                in_specs=(rep,) * 8 + (sh,) * 4 + (rep,),
                out_specs=(rep,) * 9,
                check_vma=False,
            )
        )

        n = len(rows)
        bsz = min(self.batch, n)
        bsz -= bsz % n_dev
        if bsz == 0:
            raise ValueError(
                f"co-occurrence batch ({min(self.batch, n)}) smaller than mesh ({n_dev})"
            )
        self._run_epochs(
            step, (rows, cols, logx, fx), (hw, hwc, hb, hbc), bsz,
            reshape=(n_dev, bsz // n_dev),
        )

    # combined representation (standard GloVe: w + wc)
    @property
    def syn0(self):
        return self.w + self.wc

    def get_word_vector(self, word: str) -> np.ndarray | None:
        i = self.cache.index_of(word)
        return None if i < 0 else np.asarray(self.syn0[i])

    def similarity(self, w1: str, w2: str) -> float:
        a, b = self.get_word_vector(w1), self.get_word_vector(w2)
        if a is None or b is None:
            return float("nan")
        return float(np.dot(a, b) / (np.linalg.norm(a) * np.linalg.norm(b) + 1e-9))
