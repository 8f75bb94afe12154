"""SequenceVectors engine + Word2Vec + ParagraphVectors.

Parity with:
  * SequenceVectors (`models/sequencevectors/SequenceVectors.java:51`) — the
    generic trainer over element sequences (words, labelled docs, graph
    walks), with elements_learning_algorithm (SkipGram/CBOW) and
    sequence_learning_algorithm (DBOW/DM)
  * Word2Vec (`models/word2vec/Word2Vec.java:32`) — builder config: layer
    size, window, min word frequency, negative sampling, HS, subsampling,
    lr linear decay to min_learning_rate
  * ParagraphVectors (`models/paragraphvectors/ParagraphVectors.java`) —
    DBOW/DM with label vectors in the shared lookup table + `infer_vector`
    for unseen documents

TPU-first: the Hogwild worker threads (`SequenceVectors.java:289`) are
replaced by host-side pair generation + device-batched SGD (see
`embeddings.py`); accuracy targets are the reference's NLP suite style
(similarity sanity, nearest-neighbor checks) rather than bitwise parity.
"""
from __future__ import annotations

import logging
from typing import Dict, Iterable, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from .embeddings import (InMemoryLookupTable, WordVectorsModel,
                         make_cbow_step, make_epoch_runner,
                         make_skipgram_corpus_runner, make_skipgram_step,
                         pad_scan_length)
from .sentence_iterator import (BasicLabelAwareIterator, LabelAwareIterator,
                                LabelsSource, SentenceIterator)
from .tokenization import DefaultTokenizerFactory, TokenizerFactory
from .vocab import VocabCache, VocabConstructor, VocabWord
from ..telemetry.compile_watch import watch_compiles
from ..telemetry.runtime import span as _span

log = logging.getLogger("deeplearning4j_tpu")

__all__ = ["SequenceVectors", "Word2Vec", "ParagraphVectors"]


class SequenceVectors(WordVectorsModel):
    """Generic embedding trainer over sequences of string elements."""

    def __init__(self,
                 layer_size: int = 100,
                 window_size: int = 5,
                 min_word_frequency: int = 1,
                 learning_rate: float = 0.025,
                 min_learning_rate: float = 1e-4,
                 negative: int = 5,
                 use_hierarchic_softmax: bool = False,
                 sampling: float = 0.0,
                 epochs: int = 1,
                 batch_size: int = 512,
                 seed: int = 12345,
                 elements_learning_algorithm: str = "skipgram",
                 sequence_learning_algorithm: str = "dbow",
                 train_elements: bool = True,
                 train_sequences: bool = False):
        self.layer_size = int(layer_size)
        self.window_size = int(window_size)
        self.min_word_frequency = int(min_word_frequency)
        self.learning_rate = float(learning_rate)
        self.min_learning_rate = float(min_learning_rate)
        self.negative = int(negative)
        self.use_hs = bool(use_hierarchic_softmax)
        self.sampling = float(sampling)
        self.epochs = int(epochs)
        self.batch_size = int(batch_size)
        self.seed = int(seed)
        self.elements_algo = elements_learning_algorithm.lower()
        self.sequence_algo = sequence_learning_algorithm.lower()
        self.train_elements = train_elements
        self.train_sequences = train_sequences
        self.vocab: Optional[VocabCache] = None
        self.lookup_table: Optional[InMemoryLookupTable] = None
        self._np_rng = np.random.default_rng(seed)

    # -- corpus plumbing (overridden by subclasses) ---------------------
    def _sequences(self) -> Iterable[Tuple[List[str], List[str]]]:
        """Yield (tokens, labels) pairs."""
        raise NotImplementedError

    # ------------------------------------------------------------------
    def build_vocab(self):
        seqs = list(self._sequences())
        self.vocab = VocabConstructor(self.min_word_frequency).build_vocab(
            (toks for toks, _ in seqs), (labels for _, labels in seqs))
        self.lookup_table = InMemoryLookupTable(
            self.vocab, self.layer_size, seed=self.seed,
            use_hs=self.use_hs, negative=self.negative)
        return seqs

    def _keep_probs(self, idx: np.ndarray) -> np.ndarray:
        """Frequent-word subsampling keep-probability (reference `sampling`
        config) — single definition shared by both training paths."""
        counts = self.vocab.counts_array()
        freq = counts[idx] / counts.sum()
        return np.minimum(1.0, np.sqrt(self.sampling / freq)
                          + self.sampling / freq)

    def _subsample(self, idx: np.ndarray) -> np.ndarray:
        if self.sampling <= 0:
            return idx
        return idx[self._np_rng.random(len(idx)) < self._keep_probs(idx)]

    def _to_indices(self, tokens: Sequence[str]) -> np.ndarray:
        idx = [self.vocab.index_of(t) for t in tokens]
        return np.array([i for i in idx if i >= 0], np.int32)

    def _flatten_corpus(self, seqs, subsample: bool = True):
        """Flatten the corpus to (word_indices, sentence_ids), optionally
        with subsampling applied — the device-side SGNS runner's input.
        One pass of dict lookups over all tokens, then pure numpy."""
        g = {w: vw.index for w, vw in self.vocab._words.items()}.get
        flat = np.fromiter((g(t, -1) for toks, _ in seqs for t in toks),
                           np.int32)
        lens = np.fromiter((len(toks) for toks, _ in seqs), np.int64)
        sid = np.repeat(np.arange(len(lens), dtype=np.int32), lens)
        keep = flat >= 0
        flat, sid = flat[keep], sid[keep]
        if subsample and self.sampling > 0 and len(flat):
            m = self._np_rng.random(len(flat)) < self._keep_probs(flat)
            flat, sid = flat[m], sid[m]
        return flat, sid

    def _gen_pairs_sg_fast(self, seqs) -> Dict[str, np.ndarray]:
        """Fully vectorized skip-gram pair generation: the whole corpus is
        flattened into one index array with sentence ids, and for each window
        offset d the (center, context) pairs come from boolean masks — W
        numpy passes instead of a Python loop per token (the host-side
        bottleneck the reference spreads over Hogwild threads,
        `SequenceVectors.java:289`). Keeps the per-center random reduced
        window b ~ U[1, W] semantics of `SkipGram.java`."""
        flat_parts, sid_parts = [], []
        for si, (tokens, _labels) in enumerate(seqs):
            idx = self._subsample(self._to_indices(tokens))
            if len(idx) < 2:
                continue
            flat_parts.append(idx)
            sid_parts.append(np.full(len(idx), si, np.int64))
        if not flat_parts:
            return {}
        flat = np.concatenate(flat_parts)
        sid = np.concatenate(sid_parts)
        n = len(flat)
        W = self.window_size
        b = self._np_rng.integers(1, W + 1, n)
        centers, ctxs = [], []
        for d in range(1, W + 1):
            same = sid[:-d] == sid[d:]
            right = same & (d <= b[:-d])   # center i  -> context i+d
            left = same & (d <= b[d:])     # center i+d -> context i
            centers.append(flat[:-d][right])
            ctxs.append(flat[d:][right])
            centers.append(flat[d:][left])
            ctxs.append(flat[:-d][left])
        c = np.concatenate(centers).astype(np.int32)
        x = np.concatenate(ctxs).astype(np.int32)
        return {"sg": (c, x)} if len(c) else {}

    def _gen_pairs(self, seqs) -> Dict[str, np.ndarray]:
        """Generate training examples host-side (vectorized per sentence)."""
        if (self.train_elements and not self.train_sequences
                and self.elements_algo == "skipgram"):
            return self._gen_pairs_sg_fast(seqs)
        sg_c, sg_x = [], []
        cb_c, cb_x = [], []
        seq_c, seq_x = [], []
        W = self.window_size
        cbow = self.elements_algo == "cbow"
        dm = self.sequence_algo == "dm"
        for tokens, labels in seqs:
            idx = self._subsample(self._to_indices(tokens))
            n = len(idx)
            if n < 2 and not labels:
                continue
            label_idx = [self.vocab.index_of(l) for l in labels]
            label_idx = [i for i in label_idx if i >= 0]
            bs = self._np_rng.integers(1, W + 1, n) if n else np.zeros(0, int)
            for i in range(n):
                b = bs[i]
                lo, hi = max(0, i - b), min(n, i + b + 1)
                ctx = np.concatenate([idx[lo:i], idx[i + 1:hi]])
                if len(ctx) == 0:
                    continue
                if self.train_elements:
                    if cbow:
                        pad = np.full(2 * W, -1, np.int32)
                        pad[:len(ctx)] = ctx[:2 * W]
                        cb_c.append(idx[i])
                        cb_x.append(pad)
                    else:
                        for c in ctx:
                            sg_c.append(idx[i])
                            sg_x.append(c)
                if self.train_sequences and label_idx:
                    if dm:
                        # DM: doc vector joins the averaged context
                        pad = np.full(2 * W + 1, -1, np.int32)
                        pad[:min(len(ctx), 2 * W)] = ctx[:2 * W]
                        pad[-1] = label_idx[0]
                        seq_c.append(idx[i])
                        seq_x.append(pad)
                    else:
                        # DBOW: doc vector predicts each word
                        for l in label_idx:
                            seq_c.append(l)
                            seq_x.append(idx[i])
        out = {}
        if sg_c:
            out["sg"] = (np.array(sg_c, np.int32), np.array(sg_x, np.int32))
        if cb_c:
            out["cb"] = (np.array(cb_c, np.int32), np.stack(cb_x))
        if seq_c:
            if dm:
                out["dm"] = (np.array(seq_c, np.int32), np.stack(seq_x))
            else:
                out["dbow"] = (np.array(seq_c, np.int32),
                               np.array(seq_x, np.int32))
        return out

    # ------------------------------------------------------------------
    def _corpus_key(self):
        """Identity of the token source: a new vocab, a swapped iterator,
        or a swapped tokenizer invalidates the flattened-corpus cache.
        The key holds STRONG references (compared by identity below), so
        a GC'd-then-reused id can never produce a false hit. In-place
        mutation of the collection BEHIND an unchanged iterator object is
        not detectable — call reset_corpus_cache() after doing that."""
        src = getattr(self, "sentence_iterator", None)
        if src is None:
            src = getattr(self, "iterator", None)
        return (self.vocab, src, getattr(self, "tokenizer_factory", None))

    @staticmethod
    def _same_key(a, b) -> bool:
        return (a is not None and b is not None and len(a) == len(b)
                and all(x is y for x, y in zip(a, b)))

    def reset_corpus_cache(self):
        """Drop the cached flattened corpus (next fit re-tokenizes)."""
        self._sg_flat_cache = None

    def fit(self):
        sg_fast = (self.train_elements and not self.train_sequences
                   and self.elements_algo == "skipgram" and not self.use_hs
                   and self.negative > 0)
        if self.vocab is None:
            seqs = self.build_vocab()
        elif (sg_fast and getattr(self, "_sg_flat_cache", None) is not None
                and self._same_key(self._sg_flat_cache[0],
                                   self._corpus_key())):
            # steady-state epochs on an unchanged corpus: skip host
            # re-tokenization entirely (equivalent to running epochs=N in
            # one fit, which flattens once)
            seqs = None
        else:
            seqs = list(self._sequences())
        table = self.lookup_table
        if sg_fast:
            return self._fit_sg_corpus(seqs)
        sg_step = make_skipgram_step(table)
        cb_step = (make_cbow_step(table, self.window_size)
                   if (self.elements_algo == "cbow"
                       or self.sequence_algo == "dm") else None)
        rng = jax.random.PRNGKey(self.seed)
        syn0, syn1, syn1neg = table.syn0, table.syn1, table.syn1neg
        if syn1 is None:
            syn1 = jnp.zeros((1, 1), jnp.float32)
        if syn1neg is None:
            syn1neg = jnp.zeros((1, 1), jnp.float32)

        runners = {}
        for epoch in range(self.epochs):
            with _span("host/pair_gen"):
                pairs = self._gen_pairs(seqs)
            tasks = []
            if "sg" in pairs:
                tasks.append(("sg", sg_step) + pairs["sg"])
            if "cb" in pairs:
                tasks.append(("cb", cb_step) + pairs["cb"])
            if "dm" in pairs:
                # DM trains through the cbow step with doc in context
                dm_step = cb_step or make_cbow_step(table, self.window_size)
                tasks.append(("dm", dm_step) + pairs["dm"])
            if "dbow" in pairs:
                tasks.append(("dbow", sg_step) + pairs["dbow"])
            total = sum(len(t[2]) for t in tasks) * self.epochs or 1
            done = epoch * (total // self.epochs)
            for kind, step, centers, contexts in tasks:
                n = len(centers)
                perm = self._np_rng.permutation(n)
                centers, contexts = centers[perm], contexts[perm]
                B = self._pair_round_batch(self.batch_size)
                pad = (-n) % B
                if pad:
                    centers = np.concatenate([centers, centers[:pad]])
                    if contexts.ndim == 1:
                        contexts = np.concatenate([contexts, contexts[:pad]])
                    else:
                        contexts = np.concatenate([contexts, contexts[:pad]],
                                                  axis=0)
                T = len(centers) // B
                # one scanned device dispatch per (task, epoch): per-step lr
                # keeps the reference's linear decay to min_learning_rate.
                # Scan length is bucketed (padded steps get lr=0, exact
                # no-ops) so pair-count jitter between epochs doesn't
                # recompile the epoch graph.
                T2 = pad_scan_length(T)
                frac = np.minimum(1.0, (done + np.arange(T2) * B) / total)
                lrs = np.maximum(self.min_learning_rate,
                                 self.learning_rate * (1.0 - frac))
                lrs[T:] = 0.0
                centers = np.resize(centers, (T2 * B,))
                contexts = np.resize(contexts,
                                     (T2 * B,) + contexts.shape[1:])
                rng, k = jax.random.split(rng)
                keys = jax.random.split(k, T2)
                runner = runners.get(kind)
                if runner is None:
                    runner = runners[kind] = watch_compiles(
                        make_epoch_runner(step), f"word2vec/{kind}_epoch")
                with _span("device/dispatch", kind=f"w2v_{kind}_epoch"):
                    syn0, syn1, syn1neg, _loss = runner(
                        syn0, syn1, syn1neg,
                        self._pair_place(
                            jnp.asarray(centers.reshape((T2, B)))),
                        self._pair_place(jnp.asarray(contexts.reshape(
                            (T2, B) + contexts.shape[1:]))),
                        jnp.asarray(lrs, jnp.float32), keys)
                done += T * B
        table.syn0 = syn0
        if table.use_hs:
            table.syn1 = syn1
        if table.negative > 0:
            table.syn1neg = syn1neg
        return self

    def _fit_sg_corpus(self, seqs):
        """SGNS fast path: corpus on device, windows + negatives generated
        inside the scanned step (see make_skipgram_corpus_runner)."""
        table = self.lookup_table
        runner_key = (id(table), self.window_size)
        if getattr(self, "_sg_runner_key", None) != runner_key:
            self._sg_runner = watch_compiles(
                make_skipgram_corpus_runner(table, self.window_size),
                "word2vec/sgns_epoch")
            self._sg_runner_key = runner_key
        runner = self._sg_runner
        # fold the per-model fit count into the stream so INCREMENTAL fits
        # continue training with fresh shuffles/negatives instead of
        # replaying epoch 1 byte-for-byte (the old stateful np_rng gave
        # this implicitly; a bare PRNGKey(seed) would not — review r5)
        fit_idx = getattr(self, "_sg_fit_count", 0)
        self._sg_fit_count = fit_idx + 1
        rng = jax.random.fold_in(jax.random.PRNGKey(self.seed), fit_idx)
        syn0, syn1neg = table.syn0, table.syn1neg
        # batch_size counts PAIRS (as in the pair path); a center yields
        # ~window pairs, so derive centers-per-step from it. Additionally cap
        # by vocab size: batched-sum SGD diverges when the same row
        # accumulates many stale-param pair gradients in one step, so keep
        # expected per-row duplication ~O(window) (sequential SGD, which the
        # reference uses, saturates instead — `SkipGram.java` per-pair axpy)
        B = max(32, self.batch_size // max(1, self.window_size))
        B = min(B, max(32, self.vocab.num_words()))
        B = self._sg_round_batch(B)
        # flatten ONCE (token->index lookup is the host-side cost); per-epoch
        # subsampling only re-draws the keep mask over the fixed index
        # array. Cached across fit() calls for an unchanged (vocab,
        # iterator) — steady-state epochs pay no host re-tokenization
        key = self._corpus_key()
        cache = getattr(self, "_sg_flat_cache", None)
        if seqs is None and cache is not None and self._same_key(cache[0],
                                                                 key):
            base_flat, base_sid = cache[1], cache[2]
        else:
            with _span("host/flatten_corpus"):
                base_flat, base_sid = self._flatten_corpus(seqs,
                                                           subsample=False)
            self._sg_flat_cache = (key, base_flat, base_sid)
        if len(base_flat) < 2:
            return self
        keep_p = self._keep_probs(base_flat) if self.sampling > 0 else None
        corpus_dev = None  # device-resident when subsampling is off
        for epoch in range(self.epochs):
            if corpus_dev is None or keep_p is not None:
                if keep_p is not None:
                    m = self._np_rng.random(len(base_flat)) < keep_p
                    flat, sid = base_flat[m], base_sid[m]
                else:
                    flat, sid = base_flat, base_sid
                if len(flat) < 2:
                    continue
                corpus_dev = (jnp.asarray(flat), jnp.asarray(sid))
            n = int(corpus_dev[0].shape[0])
            T = max(1, (n + B - 1) // B)
            # bucketed scan length: token-count jitter between subsampled
            # epochs must not recompile the epoch graph (padded steps lr=0)
            T2 = pad_scan_length(T)
            # shuffled center positions, generated ON DEVICE: the epoch
            # uploads no [T2, B] position matrix (the device permutation
            # is milliseconds)
            rng, pk = jax.random.split(rng)
            pos_dev = self._sg_positions_device(pk, n, T2, B)
            # linear decay normalized by SEEN (post-filter) tokens so the lr
            # actually reaches min_learning_rate by the last epoch
            frac = np.minimum(
                1.0, (epoch + np.arange(T2) * B / n) / self.epochs)
            lrs = np.maximum(self.min_learning_rate,
                             self.learning_rate * (1.0 - frac))
            lrs[T:] = 0.0
            rng, k = jax.random.split(rng)
            with _span("device/dispatch", kind="w2v_sgns_epoch"):
                syn0, syn1neg, _loss = runner(
                    syn0, syn1neg, corpus_dev[0], corpus_dev[1],
                    pos_dev, jnp.asarray(lrs, jnp.float32), k)
        table.syn0 = syn0
        table.syn1neg = syn1neg
        return self

    def _sg_positions_device(self, key, n: int, T2: int, B: int):
        """Device-side shuffled center positions [T2, B] (wrapped to fill
        the padded scan) — replaces a per-epoch host upload."""
        fn = getattr(self, "_sg_pos_fn", None)
        if fn is None:
            from ..telemetry.compile_watch import watch_compiles

            def pos(key, n, T2, B):
                perm = jax.random.permutation(key, n)
                reps = -(-T2 * B // n)
                return jnp.tile(perm, reps)[:T2 * B].reshape(
                    T2, B).astype(jnp.int32)

            fn = watch_compiles(jax.jit(pos, static_argnums=(1, 2, 3)),
                                "nlp/sg_positions")
            self._sg_pos_fn = fn
        return self._sg_place_positions(fn(key, n, T2, B))

    # hooks for the distributed subclasses (nlp/distributed.py)
    def _sg_round_batch(self, B: int) -> int:
        return B

    def _sg_place_positions(self, pos):
        return pos

    def _pair_round_batch(self, B: int) -> int:
        """Pair-path (sg/cbow/dbow/dm) batch rounding hook."""
        return B

    def _pair_place(self, arr):
        """Pair-path batch placement hook ([T, B, ...] arrays)."""
        return arr


class Word2Vec(SequenceVectors):
    """Reference builder parity: Word2Vec.Builder().layerSize(..).windowSize(..)
    ... here as constructor kwargs + `Builder` alias."""

    def __init__(self, sentence_iterator: Optional[SentenceIterator] = None,
                 tokenizer_factory: Optional[TokenizerFactory] = None,
                 **kw):
        kw.setdefault("train_elements", True)
        kw.setdefault("train_sequences", False)
        super().__init__(**kw)
        self.sentence_iterator = sentence_iterator
        self.tokenizer_factory = tokenizer_factory or DefaultTokenizerFactory()

    def _sequences(self):
        self.sentence_iterator.reset()
        while self.sentence_iterator.has_next():
            s = self.sentence_iterator.next_sentence()
            yield self.tokenizer_factory.create(s).get_tokens(), []


class ParagraphVectors(SequenceVectors):
    """DBOW/DM document embeddings; labels live in the shared vocab/lookup
    (reference ParagraphVectors)."""

    def __init__(self, iterator: Optional[LabelAwareIterator] = None,
                 sentence_iterator: Optional[SentenceIterator] = None,
                 tokenizer_factory: Optional[TokenizerFactory] = None,
                 **kw):
        kw.setdefault("train_elements", False)
        kw.setdefault("train_sequences", True)
        super().__init__(**kw)
        if iterator is None and sentence_iterator is not None:
            iterator = BasicLabelAwareIterator(sentence_iterator)
        self.iterator = iterator
        self.tokenizer_factory = tokenizer_factory or DefaultTokenizerFactory()

    def _sequences(self):
        self.iterator.reset()
        while self.iterator.has_next_document():
            doc = self.iterator.next_document()
            toks = self.tokenizer_factory.create(doc.content).get_tokens()
            yield toks, list(doc.labels)

    # -- label-space queries -------------------------------------------
    def labels(self) -> List[str]:
        return [vw.word for vw in self.vocab.vocab_words() if vw.is_label]

    def label_vector(self, label: str) -> Optional[np.ndarray]:
        return self.word_vector(label)

    def nearest_labels(self, vec_or_text, top_n: int = 10) -> List[str]:
        if isinstance(vec_or_text, str):
            vec = self.infer_vector(vec_or_text)
        else:
            vec = np.asarray(vec_or_text)
        m = self.lookup_table.vectors_matrix()
        sims = {}
        for vw in self.vocab.vocab_words():
            if not vw.is_label:
                continue
            v = m[vw.index]
            d = np.linalg.norm(v) * (np.linalg.norm(vec) + 1e-12)
            sims[vw.word] = float(v @ vec / d) if d else 0.0
        return sorted(sims, key=sims.get, reverse=True)[:top_n]

    def infer_vector(self, text: str, steps: int = 20,
                     learning_rate: float = 0.025) -> np.ndarray:
        """Train a fresh doc vector against the FROZEN tables (reference
        `inferVector`)."""
        toks = self.tokenizer_factory.create(text).get_tokens()
        idx = self._to_indices(toks)
        if len(idx) == 0:
            return np.zeros(self.layer_size, np.float32)
        table = self.lookup_table
        D = self.layer_size
        rng = jax.random.PRNGKey(abs(hash(text)) % (2 ** 31))
        vec = jax.random.uniform(rng, (D,), jnp.float32, -0.5 / D, 0.5 / D)
        words = jnp.asarray(idx)
        syn1neg = table.syn1neg if table.negative > 0 else None
        sampler = table.sampler

        def loss_fn(v, negs):
            # DBOW inference: doc vector predicts each observed word
            up = syn1neg[words]
            pos = jax.nn.log_sigmoid(up @ v)
            un = syn1neg[negs]                     # [N, K, D]
            neg = jnp.sum(jax.nn.log_sigmoid(-jnp.einsum(
                "d,nkd->nk", v, un)), axis=-1)
            return -jnp.sum(pos + neg)

        from ..telemetry.compile_watch import watch_compiles

        def step(v, lr, k):
            negs = sampler.sample(k, (len(idx), max(1, table.negative)))
            l, g = jax.value_and_grad(loss_fn)(v, negs)
            return v - lr * g, l

        step = watch_compiles(jax.jit(step), "nlp/infer_step")

        for t in range(steps):
            rng, k = jax.random.split(rng)
            lr = learning_rate * (1.0 - t / steps)
            vec, _ = step(vec, jnp.float32(lr), k)
        return np.asarray(vec)
