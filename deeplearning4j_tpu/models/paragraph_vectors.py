"""ParagraphVectors (doc2vec): PV-DBOW / PV-DM over labeled documents.

≙ reference models/paragraphvectors/ParagraphVectors.java:37-480
(trainSentence:149, dbow:172): label (paragraph) vectors are trained
against the words of their windows through the SAME fused HS +
negative-sampling kernel as Word2Vec (inherited from
InMemoryLookupTable.iterateSample:171); ``train_words=False`` freezes
word vectors (pure DBOW).

TPU re-design: label rows are appended to the word table as a merged
``(V + n_labels, D)`` input matrix, so a label update IS a word-kernel
update with input row ``V + label_id`` — the batched scan dispatch
(``_hs_scan``, _SCAN_WIDTH batches per device call) and the NS kernel
(``_ns_step``) apply unchanged.  The previous design dispatched one
jitted call per document per epoch, paying the per-dispatch overhead
documented in word2vec.py per sentence; the merged-table scan folds
thousands of documents into each dispatch.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from deeplearning4j_tpu.models.word2vec import (
    _SCAN_WIDTH,
    Word2Vec,
    _hs_scan,
    _ns_step,
    skipgram_pairs,  # noqa: F401  (re-exported; public through this module)
)


class ParagraphVectors(Word2Vec):
    def __init__(self, train_words: bool = True, **kw):
        super().__init__(**kw)
        self.train_words = train_words
        self.labels: dict[str, int] = {}
        self.syn0_labels: jax.Array | None = None

    def fit_labeled(self, labeled_sentences) -> None:
        """labeled_sentences: iterable of (label, sentence) pairs
        (e.g. LabelAwareSentenceIterator)."""
        pairs = list(labeled_sentences)
        from deeplearning4j_tpu.nlp.sentence_iterator import (
            CollectionSentenceIterator,
        )

        sents = CollectionSentenceIterator([s for _, s in pairs])
        if len(self.cache) == 0:
            self.build_vocab(sents)
        if self.syn0 is None:
            self.reset_weights()
        for label, _ in pairs:
            if label not in self.labels:
                self.labels[label] = len(self.labels)
        key = jax.random.key(self.seed + 1)
        self.syn0_labels = (
            jax.random.uniform(key, (len(self.labels), self.layer_size)) - 0.5
        ) / self.layer_size

        if self.train_words:
            self.fit(sents)

        # PV-DBOW label pass: enumerate (label-row, word) pairs host-side
        # ONCE (≙ ParagraphVectors.dbow:172 — the label predicts each word
        # of its document), then stream them through the batched kernels
        # against the merged (V + L, D) input table.
        v = self.syn0.shape[0]
        ins_list, tgt_list = [], []
        for label, sent in pairs:
            ids = self.cache.encode(self.tokenize(sent))
            if not ids:
                continue
            ins_list.append(
                np.full(len(ids), v + self.labels[label], np.int32)
            )
            tgt_list.append(np.asarray(ids, np.int32))
        if not ins_list:
            return
        all_ins = np.concatenate(ins_list)
        all_tgts = np.concatenate(tgt_list)

        # input table = words + labels + ONE zero scratch row: padding
        # pairs point their input at the scratch row, so their syn1/
        # syn1neg deltas are exactly g*h = 0 (h is gathered before the
        # batch's scatter) and the only garbage lands on the scratch
        # row, which is dropped after training. This keeps one compiled
        # batch shape without training junk (0,0) pairs — the
        # small-corpus degradation word2vec.py's fit documents.
        d = self.syn0.shape[1]
        merged = jnp.concatenate(
            [self.syn0, self.syn0_labels,
             jnp.zeros((1, d), self.syn0.dtype)]
        )
        scratch = v + len(self.labels)
        b = self.batch_pairs
        rng = np.random.default_rng(self.seed + 2)

        # the label pass trains at a fixed lr, so "epochs" is literally
        # the same pair stream repeated; chunk a virtual epochs-fold
        # stream by modulo indexing (no epochs-sized host copies)
        n0 = len(all_ins)
        total = n0 * self.epochs

        def chunk(s, e):
            idx = np.arange(s, min(e, total)) % n0
            return all_ins[idx], all_tgts[idx]

        if self.use_hs:
            codes = jnp.asarray(self._codes)
            points = jnp.asarray(self._points)
            mask = jnp.asarray(self._mask)
            per_dispatch = _SCAN_WIDTH * b
            for s in range(0, total, per_dispatch):
                chunk_i, chunk_t = chunk(s, s + per_dispatch)
                k = _SCAN_WIDTH
                ins_k = np.full((k, b), scratch, np.int32)
                tgts_k = np.zeros((k, b), np.int32)
                lrs_k = np.zeros((k,), np.float32)
                ins_k.reshape(-1)[: len(chunk_i)] = chunk_i
                tgts_k.reshape(-1)[: len(chunk_t)] = chunk_t
                # full batches + the (final) partial tail train at lr;
                # all-scratch filler batches ride at lr=0 (exact no-op)
                lrs_k[: -(-len(chunk_i) // b)] = self.lr
                merged, self.syn1 = _hs_scan(
                    merged, self.syn1, jnp.asarray(ins_k),
                    jnp.asarray(tgts_k), codes, points, mask,
                    jnp.asarray(lrs_k),
                )
        if self.negative > 0:
            # negative-sampling path (≙ iterateSample's negative branch,
            # InMemoryLookupTable.java:217-243): the label row is pulled
            # toward its words' syn1neg rows and away from unigram-table
            # draws. _ns_step offsets targets by len(merged) internally,
            # so word-id targets index syn1neg directly.
            if self._table is None:
                self._table = self.cache.unigram_table()
            table = self._table
            # the HS phase may have accumulated garbage on the scratch
            # row; NS pads must gather h=0 again for exact no-op deltas
            merged = merged.at[scratch].set(0.0)
            for s in range(0, total, b):
                chunk_i, chunk_t = chunk(s, s + b)
                if len(chunk_i) < b:
                    pad = b - len(chunk_i)
                    chunk_i = np.concatenate(
                        [chunk_i, np.full(pad, scratch, np.int32)]
                    )
                    chunk_t = np.concatenate(
                        [chunk_t, np.zeros(pad, np.int32)]
                    )
                negs = table[
                    rng.integers(0, len(table), size=(b, self.negative))
                ]
                merged, self.syn1neg = _ns_step(
                    merged, self.syn1neg, jnp.asarray(chunk_i),
                    jnp.asarray(chunk_t),
                    jnp.asarray(negs, jnp.int32), jnp.float32(self.lr),
                )

        self.syn0 = merged[:v]
        self.syn0_labels = merged[v:scratch]

    def get_label_vector(self, label: str) -> np.ndarray | None:
        i = self.labels.get(label)
        return None if i is None else np.asarray(self.syn0_labels[i])

    def infer_nearest_label(self, sentence: str) -> str | None:
        """Classify by cosine between doc's mean word vector and labels."""
        ids = self.cache.encode(self.tokenize(sentence))
        if not ids or not self.labels:
            return None
        doc = np.asarray(self.syn0)[ids].mean(0)
        mat = np.asarray(self.syn0_labels)
        sims = mat @ doc / (
            np.linalg.norm(mat, axis=1) * np.linalg.norm(doc) + 1e-9
        )
        inv = {v: k for k, v in self.labels.items()}
        return inv[int(np.argmax(sims))]
