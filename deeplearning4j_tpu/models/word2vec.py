"""Word2Vec: skip-gram with hierarchical softmax + negative sampling.

≙ reference models/word2vec/Word2Vec.java:41-640 (vocab build :247,
Huffman :340, window sampling skipGram:304/trainSentence:288, lr decay by
words seen :181) and the fused training kernel
InMemoryLookupTable.iterateSample:171-270 (exp-table sigmoid, BLAS axpy
row updates, unigram^0.75 negative table).

TPU re-design (SURVEY §7 "Word2Vec throughput" hard part): the reference
gets speed from *racy* per-pair BLAS axpy updates across threads
(Hogwild).  Here training pairs are generated host-side (numpy), batched,
and each batch is ONE jitted XLA program:

- gather input rows -> batched HS/NS dot products on the MXU ->
  scatter-add row updates (``.at[].add``, XLA scatter) for syn0/syn1.
- Within a batch, colliding row updates *accumulate* (scatter-add) rather
  than race — deterministic, and mathematically the minibatch version of
  the reference's sequential SGD.
- The dense (V, max_code_len) Huffman code/point arrays come from
  ``VocabCache.huffman_arrays`` so the HS tree walk is a dense gather.

The distributed variant (sharded batches + periodic AllReduce of deltas)
≙ Word2VecPerformer/Word2VecJobAggregator lives in ``fit_distributed``.
"""

from __future__ import annotations

import functools
import logging
from pathlib import Path
from typing import Iterable

import jax
import jax.numpy as jnp
import numpy as np

from deeplearning4j_tpu.nlp.sentence_iterator import SentenceIterator
from deeplearning4j_tpu.nlp.tokenization import DefaultTokenizer
from deeplearning4j_tpu.nlp.vocab import VocabCache

log = logging.getLogger(__name__)

MAX_EXP = 6.0  # ≙ the reference's exp-table domain
# HS batches folded into one dispatch by _hs_scan. Sized so the
# per-dispatch overhead is noise next to device time (~0.2ms/batch):
# 128 batches ≈ 24ms device work/dispatch.
# lr freshness is preserved because _hs_scan takes a per-batch lr vector.
_SCAN_WIDTH = 128


# -- jitted batch kernels -----------------------------------------------------

def _hs_math_merged(S, v, inputs, codes, points, mask, lr):
    """One HS batch update on the merged (2V, D) table.

    ``S[:v]`` is syn0, ``S[v:]`` is syn1. Merging the tables turns the
    two row scatter-adds (the hot write path, ≙ the reference's per-bit
    BLAS axpy in InMemoryLookupTable.iterateSample:171-270) into ONE
    scatter on the combined index set — measured 1.6x the split version
    on v5e (the scatter is VMEM-write-bound; one fused pass beats two).
    Keep the scatter UNSORTED: pre-sorting the indices costs an extra
    full materialization of the reordered updates and measured ~1.5x
    slower in the scanned kernel.
    """
    h = S[inputs]  # (B, D)
    w1 = S[v + points]  # (B, L, D)
    dot = jnp.einsum("bd,bld->bl", h, w1)
    f = jax.nn.sigmoid(dot)
    # saturated dots are SKIPPED, not clipped, exactly as the reference's
    # exp-table range check does (InMemoryLookupTable.iterateSample:
    # continue when |dot| >= MAX_EXP). Clipping instead keeps updating
    # saturated pairs with a constant-magnitude g, which feeds an
    # oscillating syn0<->syn1 instability that blows weights up on small
    # corpora trained for many epochs.
    in_range = (jnp.abs(dot) < MAX_EXP).astype(f.dtype)
    g = (1.0 - codes - f) * lr * mask * in_range  # (B, L)
    grad_in = jnp.einsum("bl,bld->bd", g, w1)
    d = S.shape[-1]
    rows = jnp.concatenate([inputs, (v + points).reshape(-1)])
    deltas = jnp.concatenate(
        [grad_in, (g[:, :, None] * h[:, None, :]).reshape(-1, d)]
    )
    return S.at[rows].add(deltas)


def _hs_math(syn0, syn1, inputs, codes, points, mask, lr):
    """One hierarchical-softmax batch update (pure math, jit-composable).

    inputs: (B,) input-word rows of syn0.
    codes/points/mask: (B, L) Huffman path of the target words.
    """
    v = syn0.shape[0]
    S = jnp.concatenate([syn0, syn1])
    S = _hs_math_merged(S, v, inputs, codes, points, mask, lr)
    return S[:v], S[v:]


_hs_step = jax.jit(_hs_math, donate_argnums=(0, 1))


@functools.partial(jax.jit, donate_argnums=(0, 1))
def _hs_scan(syn0, syn1, ins, tgts, codes, points, mask, lrs):
    """k HS batch updates in one dispatch (lax.scan over stacked batches).

    ins/tgts: (k, B); lrs: (k,).  The Huffman-path gather happens inside
    the scan so only the compact (k, B) index arrays cross the host
    boundary per flush. The merged (2V, D) table is concatenated ONCE
    per dispatch (16MB of copies amortized over k batches), scanned as a
    single carry, and split back at the end.
    """
    v = syn0.shape[0]
    S = jnp.concatenate([syn0, syn1])

    def body(S, xs):
        i, t, lr = xs
        return _hs_math_merged(S, v, i, codes[t], points[t], mask[t], lr), ()

    S, _ = jax.lax.scan(body, S, (ins, tgts, lrs))
    return S[:v], S[v:]


@functools.partial(jax.jit, donate_argnums=(0, 1))
def _ns_step(syn0, syn1neg, inputs, targets, negatives, lr):
    """One negative-sampling batch update.

    targets: (B,) positive rows of syn1neg; negatives: (B, K) sampled rows.
    """
    v, d = syn0.shape
    S = jnp.concatenate([syn0, syn1neg])
    h = S[inputs]  # (B, D)
    rows = jnp.concatenate([targets[:, None], negatives], axis=1)  # (B, 1+K)
    labels = jnp.concatenate(
        [jnp.ones_like(targets[:, None]), jnp.zeros_like(negatives)], axis=1
    ).astype(syn0.dtype)
    w = S[v + rows]  # (B, 1+K, D)
    dot = jnp.einsum("bd,bkd->bk", h, w)
    # negative sampling SATURATES out-of-range dots to f=1/0 (full
    # corrective update) — unlike HS, which skips them; this mirrors
    # word2vec.c's `if (f > MAX_EXP) g = (label - 1) * alpha` branch
    f = jnp.where(
        dot > MAX_EXP, 1.0,
        jnp.where(dot < -MAX_EXP, 0.0, jax.nn.sigmoid(dot)),
    )
    g = (labels - f) * lr
    grad_in = jnp.einsum("bk,bkd->bd", g, w)
    # single merged scatter (see _hs_math_merged for why)
    all_rows = jnp.concatenate([inputs, (v + rows).reshape(-1)])
    deltas = jnp.concatenate(
        [grad_in, (g[:, :, None] * h[:, None, :]).reshape(-1, d)]
    )
    S = S.at[all_rows].add(deltas)
    return S[:v], S[v:]


# -- pair generation (host) ---------------------------------------------------

def skipgram_pairs(
    sentence_ids: list[int], window: int, rng: np.random.Generator
) -> tuple[np.ndarray, np.ndarray]:
    """(input, target) pairs with per-center random window reduction
    (≙ Word2Vec.skipGram:304 — b = random % window)."""
    arr = np.asarray(sentence_ids, dtype=np.int32)
    n = len(arr)
    ins, tgts = [], []
    if n < 2:
        return np.zeros(0, np.int32), np.zeros(0, np.int32)
    bs = rng.integers(0, window, size=n)
    for i in range(n):
        span = window - int(bs[i])
        lo, hi = max(0, i - span), min(n, i + span + 1)
        for j in range(lo, hi):
            if j != i:
                ins.append(arr[j])  # context word is the input
                tgts.append(arr[i])  # center word supplies the HS path
    return np.asarray(ins, np.int32), np.asarray(tgts, np.int32)


class _PairBuffer:
    """Shared sentence→pair plumbing for ``fit`` and ``fit_distributed``.

    Buffers encoded sentences and drains them through one native
    ``sg_pairs_chunk`` pass per chunk (≙ the Java skipGram loop, now C++),
    accumulating (input, target) pair arrays until the trainer consumes
    them.  The chunk seed stream is ``seed, seed+1, ...`` so both training
    paths see identical pair enumeration for the same corpus."""

    def __init__(self, window: int, seed: int, chunk_words: int):
        self.window = window
        self.next_seed = seed
        self.chunk_words = chunk_words
        self.sents: list[np.ndarray] = []
        self.words = 0
        self._ins: list[np.ndarray] = []
        self._tgts: list[np.ndarray] = []
        self.count = 0  # pairs pending

    @staticmethod
    def words_per_chunk(batch_pairs: int, window: int) -> int:
        # E[span] ≈ window/2 each side -> ~window pairs per word; size
        # chunks to ~one batch of pairs so the lr schedule stays fresh
        return max(batch_pairs // max(window, 1), 64)

    def add(self, ids: list[int]) -> bool:
        """Buffer one encoded sentence; True when a chunk is pending."""
        if len(ids) >= 2:
            self.sents.append(np.asarray(ids, np.int32))
            self.words += len(ids)
        return self.words >= self.chunk_words

    def drain(self) -> None:
        """Enumerate pairs for all buffered sentences in one native pass."""
        if not self.sents:
            return
        from deeplearning4j_tpu import native_io

        ins, tgts = native_io.sg_pairs_chunk(
            self.sents, self.window, self.next_seed
        )
        self.next_seed += 1
        self.sents.clear()
        self.words = 0
        if len(ins):
            self._ins.append(ins)
            self._tgts.append(tgts)
            self.count += len(ins)

    def take_all(self) -> tuple[np.ndarray, np.ndarray]:
        ins = np.concatenate(self._ins) if self._ins else np.zeros(0, np.int32)
        tgts = (
            np.concatenate(self._tgts) if self._tgts else np.zeros(0, np.int32)
        )
        self._ins.clear()
        self._tgts.clear()
        self.count = 0
        return ins, tgts

    def put_back(self, ins: np.ndarray, tgts: np.ndarray) -> None:
        if len(ins):
            self._ins.append(ins)
            self._tgts.append(tgts)
            self.count += len(ins)


class Word2Vec:
    """Skip-gram embeddings (Builder fields ≙ Word2Vec.Builder:397+)."""

    def __init__(
        self,
        layer_size: int = 50,
        window: int = 5,
        min_word_frequency: int = 1,
        use_hierarchical_softmax: bool = True,
        negative: int = 0,  # number of negative samples (0 = HS only)
        lr: float = 0.025,
        min_lr: float = 1e-4,
        epochs: int = 1,
        batch_pairs: int = 4096,
        sample: float = 0.0,  # frequent-word subsampling threshold
        seed: int = 123,
        tokenizer=None,
    ):
        self.layer_size = layer_size
        self.window = window
        self.use_hs = use_hierarchical_softmax
        self.negative = negative
        self.lr = lr
        self.min_lr = min_lr
        self.epochs = epochs
        self.batch_pairs = batch_pairs
        self.sample = sample
        self.seed = seed
        self.tokenizer = tokenizer or DefaultTokenizer()
        self.cache = VocabCache(min_word_frequency)
        self.syn0: jax.Array | None = None
        self.syn1: jax.Array | None = None
        self.syn1neg: jax.Array | None = None
        self._codes = self._points = self._mask = None
        self._table: np.ndarray | None = None

    # -- vocab -------------------------------------------------------------
    def tokenize(self, sentence: str) -> list[str]:
        return self.tokenizer.tokens(sentence)

    def build_vocab(self, sentences: SentenceIterator) -> None:
        """≙ Word2Vec.buildVocab:247 + buildBinaryTree:340."""
        self.cache.fit(self.tokenize(s) for s in sentences)
        self.cache.build_huffman()
        self._codes, self._points, self._mask = self.cache.huffman_arrays()
        if self.negative > 0:
            self._table = self.cache.unigram_table()

    def reset_weights(self) -> None:
        """≙ Word2Vec.resetWeights:350 / InMemoryLookupTable init."""
        v, d = len(self.cache), self.layer_size
        key = jax.random.key(self.seed)
        self.syn0 = (jax.random.uniform(key, (v, d)) - 0.5) / d
        self.syn1 = jnp.zeros((max(v - 1, 1), d))
        self.syn1neg = jnp.zeros((v, d))

    # -- training ----------------------------------------------------------
    def _subsample(self, ids: list[int], rng: np.random.Generator) -> list[int]:
        if self.sample <= 0:
            return ids
        total = self.cache.total_word_count
        out = []
        for i in ids:
            freq = self.cache.vocab[self.cache.index_to_word[i]].count / total
            keep = (np.sqrt(freq / self.sample) + 1) * (self.sample / freq)
            if rng.random() < keep:
                out.append(i)
        return out

    def fit(self, sentences: SentenceIterator) -> None:
        """≙ Word2Vec.fit:93-203 (multithreaded Hogwild loop -> batched
        jitted scatter-add steps with linear lr decay by words seen)."""
        if len(self.cache) == 0:
            self.build_vocab(sentences)
        if self.syn0 is None:
            self.reset_weights()

        rng = np.random.default_rng(self.seed)
        total_words = max(self.cache.total_word_count * self.epochs, 1)
        words_seen = 0

        codes = jnp.asarray(self._codes)
        points = jnp.asarray(self._points)
        mask = jnp.asarray(self._mask)
        table = jnp.asarray(self._table) if self._table is not None else None

        buf = _PairBuffer(
            self.window,
            self.seed,
            _PairBuffer.words_per_chunk(self.batch_pairs, self.window),
        )

        # HS-only training queues full batches (each with its own lr
        # snapshot — _hs_scan applies a per-batch lr vector) and ships
        # them _SCAN_WIDTH at a time: one dispatch ≈ 12ms of device work,
        # so the per-dispatch overhead stops dominating. Mixed
        # HS+NS training keeps the per-batch path (the NS kernel needs
        # host-side negative sampling between batches).
        scan_path = self.use_hs and self.negative == 0
        batchq: list[tuple[np.ndarray, np.ndarray, float]] = []

        def dispatch_queue():
            if not batchq:
                return
            K = _SCAN_WIDTH
            b = self.batch_pairs
            # pad to the fixed scan width with lr=0 no-op batches (g is
            # proportional to lr, so a zero-lr batch changes nothing) —
            # one compiled program regardless of queue fill
            ins_k = np.zeros((K, b), np.int32)
            tgts_k = np.zeros((K, b), np.int32)
            lrs_k = np.zeros((K,), np.float32)
            for j, (bi, bt, blr) in enumerate(batchq):
                ins_k[j], tgts_k[j], lrs_k[j] = bi, bt, blr
            self.syn0, self.syn1 = _hs_scan(
                self.syn0, self.syn1, jnp.asarray(ins_k), jnp.asarray(tgts_k),
                codes, points, mask, jnp.asarray(lrs_k),
            )
            batchq.clear()

        def flush(train_tail: bool = False):
            buf.drain()
            if buf.count == 0:
                if train_tail:
                    dispatch_queue()
                return
            ins, tgts = buf.take_all()
            b = self.batch_pairs
            n_full = len(ins) // b
            lr_now = getattr(self, "_lr_now", self.lr)
            for k in range(n_full):
                sl = slice(k * b, (k + 1) * b)
                if scan_path:
                    batchq.append((ins[sl], tgts[sl], lr_now))
                    if len(batchq) == _SCAN_WIDTH:
                        dispatch_queue()
                else:
                    self._train_batch(
                        ins[sl], tgts[sl], codes, points, mask, table, rng
                    )
            tail = len(ins) - n_full * b
            if train_tail and tail:
                # pad the final partial batch; on the scan path it is
                # queued and flushed through dispatch_queue with the
                # other buffered batches, otherwise it trains via the
                # per-batch step
                pad = b - tail
                ins_t = np.concatenate([ins[-tail:], np.zeros(pad, np.int32)])
                tgts_t = np.concatenate([tgts[-tail:], np.zeros(pad, np.int32)])
                if scan_path:
                    batchq.append((ins_t, tgts_t, lr_now))
                else:
                    self._train_batch(
                        ins_t, tgts_t, codes, points, mask, table, rng
                    )
            elif tail:
                buf.put_back(ins[-tail:], tgts[-tail:])
            if train_tail:
                dispatch_queue()

        # pair enumeration happens once per chunk in native code; buffering
        # sentences (not pairs) keeps the Python loop to encode+subsample.
        # Chunks hold ~one batch of pairs so the lr schedule stays fresh
        # (batching many steps behind one stale lr measurably hurts
        # small-corpus convergence); _hs_scan still folds multi-batch
        # flushes into one dispatch
        for _ in range(self.epochs):
            sentences.reset()
            for sent in sentences:
                ids = self._subsample(self.cache.encode(self.tokenize(sent)), rng)
                words_seen += len(ids)
                self._lr_now = max(
                    self.min_lr, self.lr * (1.0 - words_seen / total_words)
                )
                if buf.add(ids):
                    flush()
            # epoch boundary: train all *full* batches buffered; a
            # sub-batch tail carries over to the next epoch (padding it
            # with junk (0,0) pairs every epoch measurably degrades
            # small-corpus embeddings — only the single final flush pads)
            flush()
        flush(train_tail=True)

    def _train_batch(self, ins, tgts, codes, points, mask, table, rng):
        lr = jnp.float32(getattr(self, "_lr_now", self.lr))
        ins_j = jnp.asarray(ins)
        tgts_j = jnp.asarray(tgts)
        if self.use_hs:
            self.syn0, self.syn1 = _hs_step(
                self.syn0, self.syn1, ins_j, codes[tgts_j], points[tgts_j],
                mask[tgts_j], lr,
            )
        if self.negative > 0 and table is not None:
            neg_idx = rng.integers(0, len(table), size=(len(ins), self.negative))
            negatives = table[jnp.asarray(neg_idx, jnp.int32)]
            self.syn0, self.syn1neg = _ns_step(
                self.syn0, self.syn1neg, ins_j, tgts_j, negatives, lr
            )

    # -- distributed (≙ Word2VecPerformer + Word2VecJobAggregator) ----------
    def fit_distributed(self, sentences: SentenceIterator, mesh=None) -> None:
        """Data-parallel Word2Vec: each device trains on a shard of each
        pair-batch and the parameter *deltas* are averaged — reproducing the
        master-side delta merge (Word2VecJobAggregator.java:23-36) as an
        in-graph pmean over the mesh."""
        from jax import shard_map
        from jax.sharding import PartitionSpec as P

        from deeplearning4j_tpu.parallel import mesh as mesh_lib

        mesh = mesh or mesh_lib.data_parallel_mesh()
        n_dev = mesh.devices.size

        if len(self.cache) == 0:
            self.build_vocab(sentences)
        if self.syn0 is None:
            self.reset_weights()

        codes = jnp.asarray(self._codes)
        points = jnp.asarray(self._points)
        mask = jnp.asarray(self._mask)

        def per_device(syn0, syn1, ins, cds, pts, msk, lr):
            new0, new1 = _hs_math(syn0, syn1, ins[0], cds[0], pts[0], msk[0], lr)
            # average deltas across devices == average of updated params
            # since all started from the same replicated copy
            new0 = jax.lax.pmean(new0, mesh_lib.DATA_AXIS)
            new1 = jax.lax.pmean(new1, mesh_lib.DATA_AXIS)
            return new0, new1

        axis = mesh_lib.DATA_AXIS
        step = jax.jit(
            shard_map(
                per_device,
                mesh=mesh,
                in_specs=(P(), P(), P(axis), P(axis), P(axis), P(axis), P()),
                out_specs=(P(), P()),
                check_vma=False,
            )
        )

        b = self.batch_pairs - self.batch_pairs % n_dev
        buf = _PairBuffer(
            self.window, self.seed, _PairBuffer.words_per_chunk(b, self.window)
        )
        sentences.reset()

        def train_full_batches():
            while buf.count >= b:
                allin, alltg = buf.take_all()
                batch_i, batch_t = allin[:b], alltg[:b]
                buf.put_back(allin[b:], alltg[b:])
                per = b // n_dev
                bi = jnp.asarray(batch_i).reshape(n_dev, per)
                bt = jnp.asarray(batch_t)
                self.syn0, self.syn1 = step(
                    self.syn0, self.syn1, bi,
                    codes[bt].reshape(n_dev, per, codes.shape[1]),
                    points[bt].reshape(n_dev, per, points.shape[1]),
                    mask[bt].reshape(n_dev, per, mask.shape[1]),
                    jnp.float32(self.lr),
                )

        for sent in sentences:
            ids = self.cache.encode(self.tokenize(sent))
            if buf.add(ids):
                buf.drain()
            train_full_batches()
        buf.drain()
        train_full_batches()  # tail < b pairs is dropped, as before

    # -- WordVectors API (≙ WordVectorsImpl.java:361) -----------------------
    def get_word_vector(self, word: str) -> np.ndarray | None:
        i = self.cache.index_of(word)
        return None if i < 0 else np.asarray(self.syn0[i])

    def _normed(self) -> np.ndarray:
        m = np.asarray(self.syn0)
        return m / (np.linalg.norm(m, axis=1, keepdims=True) + 1e-9)

    def similarity(self, w1: str, w2: str) -> float:
        """Cosine similarity (≙ WordVectorsImpl.similarity)."""
        a, b = self.get_word_vector(w1), self.get_word_vector(w2)
        if a is None or b is None:
            return float("nan")
        return float(
            np.dot(a, b) / ((np.linalg.norm(a) * np.linalg.norm(b)) + 1e-9)
        )

    def words_nearest(self, word_or_vec, top: int = 10, exclude: set[str] = frozenset()) -> list[str]:
        """≙ WordVectorsImpl.wordsNearest — cosine ranking."""
        if isinstance(word_or_vec, str):
            vec = self.get_word_vector(word_or_vec)
            exclude = set(exclude) | {word_or_vec}
            if vec is None:
                return []
        else:
            vec = np.asarray(word_or_vec)
        normed = self._normed()
        q = vec / (np.linalg.norm(vec) + 1e-9)
        sims = normed @ q
        order = np.argsort(-sims)
        out = []
        for i in order:
            w = self.cache.word_for(int(i))
            if w not in exclude:
                out.append(w)
            if len(out) >= top:
                break
        return out

    def _answer_analogy(self, normed, a, b, c, d):
        """Top-1 analogy answer against a pre-normalized matrix:
        True/False, or None when any word is out of vocabulary (the
        word2vec.c skip convention). ONE implementation behind both
        accuracy surfaces."""
        va, vb, vc = (self.get_word_vector(w) for w in (a, b, c))
        if va is None or vb is None or vc is None or d not in self.cache:
            return None
        q = vb - va + vc
        sims = normed @ (q / (np.linalg.norm(q) + 1e-9))
        exclude = {a, b, c}
        for i in np.argsort(-sims):
            w = self.cache.word_for(int(i))
            if w not in exclude:
                return w == d
        return False

    def accuracy(self, questions: list[tuple[str, str, str, str]]) -> float:
        """Analogy accuracy a:b :: c:d (≙ WordVectors.accuracy)."""
        return self.accuracy_report({"all": questions})["TOTAL"]["accuracy"]

    def accuracy_report(
        self, path_or_categories
    ) -> dict[str, dict[str, float]]:
        """Per-category analogy report from the Google questions-words
        format (≙ the reference's ``accuracy`` surface consuming the
        standard file, WordVectorsImpl.java — which took the raw lines;
        here also a path or pre-parsed {category: [(a,b,c,d), ...]}).

        Returns ``{category: {"accuracy", "correct", "total",
        "skipped"}}`` plus a ``"TOTAL"`` row; ``total`` counts questions
        whose four words are all in vocabulary (the word2vec.c
        convention — OOV questions are skipped, reported per category).
        """
        if isinstance(path_or_categories, (str, Path)):
            cats = parse_questions_words(path_or_categories)
        else:
            cats = dict(path_or_categories)
        # normalize the matrix ONCE: the standard questions-words file
        # holds ~19.5K analogies, and a per-question _normed() would
        # redo the full-vocab normalization every time
        normed = self._normed()
        report: dict[str, dict[str, float]] = {}
        g_corr = g_tot = g_skip = 0
        for cat, questions in cats.items():
            corr = tot = skip = 0
            for a, b, c, d in questions:
                ans = self._answer_analogy(normed, a, b, c, d)
                if ans is None:
                    skip += 1
                    continue
                tot += 1
                corr += bool(ans)
            report[cat] = {
                "accuracy": corr / tot if tot else 0.0,
                "correct": corr, "total": tot, "skipped": skip,
            }
            g_corr += corr
            g_tot += tot
            g_skip += skip
        report["TOTAL"] = {
            "accuracy": g_corr / g_tot if g_tot else 0.0,
            "correct": g_corr, "total": g_tot, "skipped": g_skip,
        }
        return report


def parse_questions_words(path: str | Path) -> dict[str, list[tuple]]:
    """Parse the Google ``questions-words.txt`` analogy format:
    ``: category`` headers followed by ``a b c d`` lines (≙ the file the
    reference's WordVectorsImpl accuracy surface consumes). Lines that
    are not exactly four tokens are skipped, like word2vec.c's
    compute-accuracy."""
    cats: dict[str, list[tuple]] = {}
    current = "uncategorized"
    with open(path) as f:
        for line in f:
            line = line.strip()
            if not line:
                continue
            if line.startswith(":"):
                current = line[1:].strip() or current
                cats.setdefault(current, [])
                continue
            parts = line.split()
            if len(parts) == 4:
                cats.setdefault(current, []).append(tuple(parts))
    return cats
