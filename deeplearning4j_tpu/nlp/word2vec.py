"""SequenceVectors engine + Word2Vec front (reference: deeplearning4j-nlp
``models/sequencevectors/SequenceVectors`` and ``models/word2vec/Word2Vec``).

Architecture (vs the reference, SURVEY §3.6): the reference trains with N
Java worker threads each dispatching one fused ``SkipGramRound`` JNI kernel
per (center, context) pair. The TPU rebuild keeps the same statistical
procedure — frequency-pruned vocab, frequent-word subsampling, per-position
reduced window, unigram^0.75 negative sampling or Huffman hierarchical
softmax, linear LR decay — but restructures the hot loop hardware-first
(the choices below were made on a set-up that is gone; not measured on
this chip):

- DEFAULT paths — skip-gram AND CBOW (``_train_windowed``, round 4): the
  corpus is uploaded ONCE and lives on device; every dispatch derives its
  windows there (shifted slices), draws negatives from a pre-drawn pool,
  and scatter-updates only the touched table rows. Skip-gram additionally
  compacts its (center, context) pairs densely before training.
- custom streams (ParagraphVectors) and ``device_corpus=False`` use the
  host pair pipeline: vectorized/native pair generation buffered into
  fixed-size uint16 column blocks, staged to device from a producer
  thread (``common/background.prefetch_iter``);
- both paths run ONE jitted ``lax.scan`` block per dispatch
  (``ops/embeddings.py`` fused rounds, tables donated) and compile exactly
  ONE block shape per fit;
- the reference's ``workers`` thread knob is accepted and recorded but
  parallelism comes from batching on the MXU, not host threads.

``iterations`` follows the reference semantics (each sentence's pairs are
trained `iterations` times per epoch); ``epochs`` is the corpus pass count.
"""

from __future__ import annotations

import time
from typing import Callable, Iterable, List, Optional, Sequence

import numpy as np

from ..common import xprof
from .lookup_table import InMemoryLookupTable
from .text import (CollectionSentenceIterator, DefaultTokenizerFactory,
                   SentenceIterator, TokenizerFactory)
from .vocab import (VocabCache, VocabConstructor, build_huffman,
                    huffman_arrays, subsample_keep_probs, unigram_int_table,
                    unigram_table)


class WordVectors:
    """Query surface shared by Word2Vec/ParagraphVectors and models loaded
    from serialized vectors (reference: WordVectors interface —
    getWordVector / similarity / wordsNearest / accuracy)."""

    def __init__(self, vocab: VocabCache, table: InMemoryLookupTable):
        self.vocab = vocab
        self.lookup_table = table

    # -- basic lookups ----------------------------------------------------
    def has_word(self, word: str) -> bool:
        return word in self.vocab

    def get_word_vector(self, word: str) -> np.ndarray:
        idx = self.vocab.index_of(word)
        if idx < 0:
            raise KeyError(f"word not in vocab: {word!r}")
        return self.lookup_table.vector(idx)

    def get_word_vector_matrix(self) -> np.ndarray:
        return np.asarray(self.lookup_table.syn0)

    # -- similarity / nearest --------------------------------------------
    def similarity(self, w1: str, w2: str) -> float:
        a, b = self.get_word_vector(w1), self.get_word_vector(w2)
        na, nb = np.linalg.norm(a), np.linalg.norm(b)
        if na == 0 or nb == 0:
            return 0.0
        return float(a @ b / (na * nb))

    def words_nearest(self, word_or_vec, top_n: int = 10) -> List[str]:
        if isinstance(word_or_vec, str):
            vec = self.get_word_vector(word_or_vec)
            exclude = {self.vocab.index_of(word_or_vec)}
        else:
            vec = np.asarray(word_or_vec, dtype=np.float32)
            exclude = set()
        w = self.lookup_table.normalized()
        v = vec / max(np.linalg.norm(vec), 1e-12)
        sims = w @ v
        order = np.argsort(-sims)
        out = []
        for idx in order:
            if int(idx) in exclude:
                continue
            out.append(self.vocab.word_for(int(idx)))
            if len(out) == top_n:
                break
        return out

    def accuracy(self, questions: Sequence[Sequence[str]]) -> float:
        """Analogy accuracy: each question is (a, b, c, expected) testing
        b - a + c ≈ expected (reference: WordVectors.accuracy over the
        Google questions-words format)."""
        correct = total = 0
        for a, b, c, expected in questions:
            if not all(self.has_word(w) for w in (a, b, c, expected)):
                continue
            total += 1
            vec = (self.get_word_vector(b) - self.get_word_vector(a)
                   + self.get_word_vector(c))
            nearest = self.words_nearest(vec, top_n=4)
            preds = [w for w in nearest if w not in (a, b, c)]
            if preds and preds[0] == expected:
                correct += 1
        return correct / total if total else 0.0


class SequenceVectors(WordVectors):
    """The distributed-representation training engine; Word2Vec and
    ParagraphVectors are thin configuration fronts over it (mirrors the
    reference's SequenceVectors inheritance)."""

    def __init__(self, *, layer_size: int = 100, window: int = 5,
                 learning_rate: float = 0.025, min_learning_rate: float = 1e-4,
                 negative: int = 5, use_hierarchic_softmax: bool = False,
                 sampling: float = 0.0, min_word_frequency: int = 5,
                 iterations: int = 1, epochs: int = 1, batch_size: int = 512,
                 seed: int = 42, algorithm: str = "skipgram",
                 workers: int = 1, table_dtype: str = "float32",
                 mesh=None, table_sharding_axis: str = "model",
                 special_tokens: Sequence[str] = ()):
        if use_hierarchic_softmax:
            # DOCUMENTED DIVERGENCE: the reference can train HS and negative
            # sampling simultaneously; this engine trains exactly one output
            # path per fit. Silent dropping would serialize an untrained
            # syn1neg as if it were state — refuse instead.
            if negative == 5:      # the constructor default
                negative = 0
            elif negative > 0:
                raise ValueError(
                    "combined hierarchical-softmax + negative-sampling "
                    "training is not implemented; set negative=0 with "
                    "use_hierarchic_softmax=True (or disable HS)")
        elif negative <= 0:
            raise ValueError("need negative sampling (negative>0) or "
                             "use_hierarchic_softmax=True")
        self.layer_size = layer_size
        self.window = window
        self.learning_rate = learning_rate
        self.min_learning_rate = min_learning_rate
        self.negative = negative
        self.use_hs = use_hierarchic_softmax
        self.sampling = sampling
        self.min_word_frequency = min_word_frequency
        self.iterations = iterations
        self.epochs = epochs
        self.batch_size = batch_size
        self.seed = seed
        self.algorithm = algorithm.lower()
        if self.algorithm not in ("skipgram", "cbow"):
            raise ValueError(f"unknown algorithm {algorithm!r}")
        # Accepted for reference config parity; batching on the MXU replaces
        # host worker threads (see module docstring).
        self.workers = workers
        # "bfloat16" halves table gather/scatter HBM traffic on the
        # device-windowed path; stored vectors are cast back to float32
        # after the fit. Default stays float32 (bit-identical convergence
        # with the reference-shaped procedure).
        if table_dtype not in ("float32", "bfloat16"):
            raise ValueError(f"table_dtype must be float32|bfloat16, "
                             f"got {table_dtype!r}")
        self.table_dtype = table_dtype
        # Row-sharded syn0/syn1 over a mesh axis — the reference's
        # VoidParameterServer sharded exactly this workload (SURVEY §2.4
        # row 4); here the device-windowed block runs under shard_map with
        # psum-assembled row lookups (ops/embeddings.py sharded_skipgram).
        if mesh is not None and self.use_hs:
            raise ValueError("sharded tables support negative sampling "
                             "only (use_hierarchic_softmax=False)")
        self.mesh = mesh
        self.table_sharding_axis = table_sharding_axis
        self._special_tokens = list(special_tokens)
        self.words_per_sec: float = 0.0
        super().__init__(VocabCache(), InMemoryLookupTable(0, layer_size))

    # -- corpus encoding --------------------------------------------------
    def _encode_corpus(self, token_seqs: Iterable[List[str]]) -> List[np.ndarray]:
        enc = []
        for tokens in token_seqs:
            ids = [self.vocab.index_of(t) for t in tokens]
            ids = np.asarray([i for i in ids if i >= 0], dtype=np.int32)
            if ids.size:
                enc.append(ids)
        return enc

    def build_vocab(self, token_seqs: Iterable[List[str]]) -> None:
        self.vocab = VocabConstructor(
            self.min_word_frequency,
            special_tokens=self._special_tokens).build(token_seqs)
        if self.use_hs:
            build_huffman(self.vocab)
        self.lookup_table = InMemoryLookupTable(
            len(self.vocab), self.layer_size, seed=self.seed)
        self.lookup_table.reset_weights(self.use_hs, self.negative > 0)

    # -- pair generation (vectorized, host) -------------------------------
    def _sentence_pairs(self, ids: np.ndarray, rng: np.random.Generator,
                        keep: np.ndarray):
        """(centers, contexts) int32 arrays for one sentence: frequent-word
        subsampling then per-position reduced window b ~ U[1, window]."""
        if self.sampling > 0:
            ids = ids[rng.random(ids.size) < keep[ids]]
        n = ids.size
        if n < 2:
            return None
        W = self.window
        b = rng.integers(1, W + 1, size=n)
        offs = np.concatenate([np.arange(-W, 0), np.arange(1, W + 1)])
        pos = np.arange(n)[:, None] + offs[None, :]            # [n, 2W]
        valid = ((np.abs(offs)[None, :] <= b[:, None])
                 & (pos >= 0) & (pos < n))
        centers = np.broadcast_to(ids[:, None], valid.shape)[valid]
        contexts = ids[np.clip(pos, 0, n - 1)][valid]
        return centers, contexts

    def _sentence_windows(self, ids: np.ndarray, rng: np.random.Generator,
                          keep: np.ndarray):
        """CBOW grouping: (centers [n], contexts [n, 2W], ctx_mask [n, 2W])
        — the full reduced window per center position."""
        if self.sampling > 0:
            ids = ids[rng.random(ids.size) < keep[ids]]
        n = ids.size
        if n < 2:
            return None
        W = self.window
        b = rng.integers(1, W + 1, size=n)
        offs = np.concatenate([np.arange(-W, 0), np.arange(1, W + 1)])
        pos = np.arange(n)[:, None] + offs[None, :]
        valid = ((np.abs(offs)[None, :] <= b[:, None])
                 & (pos >= 0) & (pos < n))
        contexts = ids[np.clip(pos, 0, n - 1)] * valid
        return ids, contexts.astype(np.int32), valid.astype(np.float32)

    # -- device step ------------------------------------------------------
    # Max training rounds fused into one device dispatch: a dispatch has a
    # fixed host cost regardless of payload, so the hot loop runs a
    # lax.scan over up to this many rounds per call. 64 was chosen on a
    # set-up that is gone; not measured on this chip.
    MAX_BLOCK_ROUNDS = 64
    # A whole fit compiles exactly ONE block shape: mid-fit flushes emit
    # only full blocks (remainders carry forward), and the single final
    # tail is mask-padded up to a full block (≤63 no-op rounds ≈ 75 ms of
    # device time). Round-3 finding: the earlier pow2 tail splitting
    # compiled up to 7 shapes at ~4–15 s EACH on TPU — compilation, not
    # compute, dominated the entire fit.

    # Corpus device buffers are padded to this multiple so distinct corpus
    # sizes reuse a handful of compiled shapes.
    CORPUS_BUCKET = 1 << 16
    # Pre-drawn negative-sample pool entries (device int32, ~32 MB): the
    # NS path consumes pool windows at prime-stride offsets instead of
    # gathering the unigram table per candidate (see _make_window_block).
    NEG_POOL_SIZE = 1 << 23
    # Hierarchical-softmax round-size cap: every pair's path hits the
    # Huffman root, so summed-scatter collisions per round == round size
    # (see _round_pairs).
    HS_MAX_ROUND = 128

    @property
    def _window_centers(self) -> int:
        """Centers per device-windowed round, sized so one round trains
        ~batch_size (center, context) slots. batch_size stays the
        stability knob it is on the host path: per-round updates into one
        table row scale with examples-per-round, and a tiny vocab with a
        huge round diverges (observed: NaN at 10k slots/round over a
        12-word vocab)."""
        return max(1, self.batch_size // (2 * self.window))

    @property
    def _round_pairs(self) -> int:
        """Dense training pairs per round. Capped by vocab size: the
        scatter-add SUMS colliding row updates within a round (the
        reference applies pairs serially, each against the current row),
        so a tiny vocab with a big round compounds updates and diverges —
        measured on a 16-word vocab: ~100 expected collisions per syn1 row
        per round trains cleanly (the round-3 masked path's stable
        operating point), ~190 explodes to 1e15 norms, ~380 NaNs. 8·V
        keeps expected collisions (B·(1+K)/V ≈ 48) comfortably inside the
        stable regime while leaving any vocab ≥ ~1k at the full
        batch-size-derived round."""
        B = self._window_centers * 2 * self.window
        cap = min(B, 8 * max(len(self.vocab), 1))
        floor = max(2 * self.window, 2)
        if self.use_hs:
            # HS concentrates EVERY pair's update on the Huffman ROOT row
            # (and nearly every pair on the top tree nodes), so collisions
            # per round equal the round size itself — far past the ~190
            # summed-update stability boundary at the NS cap. Measured on
            # the 4M-word bench corpus: B=8190 NaNs, B<=HS_MAX_ROUND
            # trains cleanly (round 5). The cap must also beat the 2W
            # floor, or window>=65 would reintroduce the NaN.
            return min(max(floor, cap), self.HS_MAX_ROUND)
        return max(floor, cap)

    @property
    def _window_span(self) -> int:
        """Corpus positions consumed per packed dispatch, sized so the
        EXPECTED pair count (≤ (W+1) per position) fills MAX_BLOCK_ROUNDS
        dense rounds of B slots."""
        return max(1, (self._round_pairs * self.MAX_BLOCK_ROUNDS)
                   // (self.window + 1))

    def _subsample_fn(self):
        """Jitted device-side frequent-word subsampling + stream
        compaction: ``(ids, sent, keep, n_full, key) -> (ids', sent',
        count)``. Same cumsum→scatter compaction as the pair packer;
        padding slots get the uint16 sentinel sentence id so window
        boundary checks fail there."""
        # keyed on window: W is baked into the closure (stream offset)
        fn = None
        cached = getattr(self, "_subsample_jit", None)
        if cached is not None and cached[0] == self.window:
            fn = cached[1]
        if fn is None:
            import jax
            import jax.numpy as jnp
            from jax import lax

            W = self.window

            @jax.jit
            def fn(ids, sent, keep_dev, n_full, key):
                N = ids.shape[0]
                iota = lax.broadcasted_iota(jnp.int32, (N,), 0)
                u = jax.random.uniform(key, (N,))
                # the stream occupies buffer slots [W, W+n_full) (front
                # pad, see _train_windowed); the compacted stream is
                # rewritten at the same W offset
                vf = ((u < keep_dev[ids.astype(jnp.int32)])
                      & (iota >= W) & (iota < W + n_full))
                dest = jnp.cumsum(vf.astype(jnp.int32)) - 1
                slot = jnp.where(vf, dest + W, N)
                ids_sub = jnp.zeros((N,), ids.dtype).at[slot].set(
                    ids, mode="drop")
                sent_sub = jnp.full(
                    # graftlint: disable=host-sync-in-step -- trace-time
                    # constant: iinfo folds into the trace, no runtime sync
                    (N,), np.iinfo(np.uint16).max,
                    sent.dtype).at[slot].set(sent, mode="drop")
                return ids_sub, sent_sub, dest[-1] + 1

            fn = xprof.register_jit("nlp/w2v_subsample", fn)
            self._subsample_jit = (self.window, fn)
        return fn

    def _make_block(self, hs_dev=None, ntable_dev=None):
        """Jitted (syn0, syn1, cols, key) -> (syn0', syn1', mean_loss)
        running a ``lax.scan`` of fused rounds.

        The column format is sized for few host→device bytes, not for
        convenience (chosen on a set-up whose host→device path was the
        bottleneck and is gone; not measured on this chip):

        - word indices travel as uint16 whenever the vocab fits (cast to
          int32 on device);
        - the per-pair float mask became a per-round valid-pair COUNT,
          expanded to a mask on device with one iota compare;
        - NS negatives never travel at all: the whole block's draws happen
          on device in ONE bulk gather from a 2^20-slot unigram^0.75 int
          table (``unigram_int_table`` — the reference's own table design)
          before the scan. Bulk ``random_bits`` + gather replaced the
          per-round searchsorted that was 65% of round-2's device profile.
        - HS configs gather Huffman paths from device-resident tables
          (``hs_dev``) by word index, as before.

        RNG divergence from the reference's host-side PCG sampling is
        DOCUMENTED (SURVEY declares statistical, not bitwise, parity).
        """
        import functools

        import jax
        import jax.numpy as jnp
        from jax import lax

        from ..ops import embeddings as E

        # Table-update lowering: scatter-add everywhere (round-3 shootout,
        # ops/embeddings.py module docstring).
        dense = len(self.vocab) <= E.DENSE_UPDATE_MAX_ROWS
        is_cbow = self.algorithm == "cbow"
        use_hs = self.use_hs
        V, K, B = len(self.vocab), self.negative, self.batch_size
        if use_hs:
            points_d, codes_d, mask_d = hs_dev
        else:
            lab = jnp.zeros((B, 1 + K), jnp.float32).at[:, 0].set(1.0)

        def pm_of(nv):
            return (lax.broadcasted_iota(jnp.int32, (B,), 0)
                    < nv).astype(jnp.float32)

        def body(carry, inp):
            s0, s1 = carry
            if is_cbow and use_hs:
                ctx, cm, c, nv, lr = inp
                c = c.astype(jnp.int32)
                s0, s1, loss = E.cbow_hs(
                    s0, s1, ctx.astype(jnp.int32), cm.astype(jnp.float32),
                    points_d[c], codes_d[c], mask_d[c], lr, pm_of(nv),
                    dense=dense)
            elif is_cbow:
                ctx, cm, tgt, nv, lr = inp
                s0, s1, loss = E.cbow(
                    s0, s1, ctx.astype(jnp.int32), cm.astype(jnp.float32),
                    tgt, lab, lr, pm_of(nv), dense=dense)
            elif use_hs:
                c, x, nv, lr = inp
                x = x.astype(jnp.int32)
                s0, s1, loss = E.skipgram_hs(
                    s0, s1, c.astype(jnp.int32), points_d[x], codes_d[x],
                    mask_d[x], lr, pm_of(nv), dense=dense)
            else:
                c, tgt, nv, lr = inp
                s0, s1, loss = E.skipgram(
                    s0, s1, c.astype(jnp.int32), tgt, lab, lr, pm_of(nv),
                    dense=dense)
            return (s0, s1), (loss, nv.astype(jnp.float32))

        def bulk_targets(key, pos3):
            """[R, B, 1+K] int32 targets for the whole block (col 0 =
            positive); collisions with the positive shifted by one (same
            shift the host path uses)."""
            T = ntable_dev.shape[0]
            bits = jax.random.bits(key, pos3.shape + (K,), jnp.uint32)
            negs = ntable_dev[(bits & (T - 1)).astype(jnp.int32)]
            negs = jnp.where(negs == pos3[..., None], (negs + 1) % V, negs)
            return jnp.concatenate([pos3[..., None], negs], axis=-1)

        @functools.partial(jax.jit, donate_argnums=(0, 1))
        def block(syn0, syn1, cols, key, blk_id):
            # fold_in runs INSIDE the jit: eager jax.random.fold_in is a
            # chain of tiny dispatches — hoisting it makes the whole block
            # one dispatch again.
            key = jax.random.fold_in(key, blk_id)
            if use_hs:
                xs = cols
            elif is_cbow:
                ctx3, cm3, c3, nv3, lr3 = cols
                tgt3 = bulk_targets(key, c3.astype(jnp.int32))
                xs = (ctx3, cm3, tgt3, nv3, lr3)
            else:
                c3, x3, nv3, lr3 = cols
                tgt3 = bulk_targets(key, x3.astype(jnp.int32))
                xs = (c3, tgt3, nv3, lr3)
            (syn0, syn1), (losses, ns) = lax.scan(body, (syn0, syn1), xs)
            # pair-weighted mean: mask-padded rounds carry zero weight, so
            # the monitored loss tracks training regardless of padding
            return (syn0, syn1,
                    (losses * ns).sum() / jnp.maximum(ns.sum(), 1.0))

        return xprof.register_jit("nlp/w2v_sg_block", block, donate=(0, 1))

    def _make_window_block(self, hs_dev=None, ntable_dev=None):
        """Packed device-windowed skip-gram block: the corpus lives ON
        DEVICE, each dispatch derives its training pairs there AND compacts
        them densely before training.

        Jitted ``(syn0, syn1, ids, sent, n_valid, p0, (lr0, lr1), key,
        blk_id) -> (syn0', syn1', mean_loss, n_pairs)`` where ``ids``/
        ``sent`` are the (subsampled, compacted) flat corpus and its
        sentence-id map — uploaded once per epoch, ~2–6 bytes/word — and
        per-dispatch host traffic is three scalars. Round-3's design
        trained every candidate slot with a validity mask: reduced windows
        (b ~ U[1, W]) plus boundary losses left only ~53% of slots live, so
        nearly half the gather/scatter bandwidth moved masked zeros.
        This block instead:

        1. derives ALL candidate pairs for a span of S = B·R/(W+1)
           positions (S·2W candidate slots) in one vectorized pass;
        2. compacts the valid (center, context) pairs with a
           cumsum→scatter into a dense buffer of capacity ⌈S·2W/B⌉·B —
           the worst case (every position realizing its full 2W window),
           so NO pair can ever be dropped; the span size S targets the
           EXPECTED fill E[min(b,left)+min(b,right)] ≤ E[2b] = W+1 pairs
           per position ≈ R dense rounds;
        3. trains ceil(count/B) fully-dense rounds under a
           ``lax.while_loop`` — unfilled capacity never executes, and the
           single partial tail round wastes <1% instead of 47%.

        Dense packing is pure bookkeeping (≈8 bytes/slot) next to a
        training round (≈4·(2+K)·D bytes/slot of table gather+scatter), so
        compaction costs ~1% and the masked-slot waste converts almost
        entirely into throughput. The statistical procedure (reduced
        windows, subsampled stream, NS/HS paths, linear LR decay, corpus
        pair order) is unchanged from round 3.
        """
        import functools

        import jax
        import jax.numpy as jnp
        from jax import lax

        from ..ops import embeddings as E

        is_hs = self.use_hs
        V, K, W = len(self.vocab), self.negative, self.window
        B = self._round_pairs                # dense pairs per round
        R = self.MAX_BLOCK_ROUNDS
        S = self._window_span                # positions per dispatch
        # worst-case capacity (every slot valid), rounded up to full rounds
        C = -(-(S * 2 * W) // B) * B
        if is_hs:
            points_d, codes_d, mask_d = hs_dev
            self._win_negpool = jnp.zeros((8,), jnp.int32)
        else:
            lab = jnp.zeros((B, 1 + K), jnp.float32).at[:, 0].set(1.0)
            # Pre-drawn negative POOL, walked with a prime stride per round
            # instead of a per-dispatch C×K table gather (round-4 trace:
            # that gather cost MORE than the training loop). word2vec.c
            # itself walks its 1e8-slot table with an LCG — a fixed
            # pseudo-random pool consumed at pseudo-random offsets is the
            # same statistical device, built from the unigram^0.75 table.
            self._win_negpool = self._build_negpool(ntable_dev, B * K)

        def pack(ids, sent, n_valid, p0, kb):
            """Shared ``_pack_span`` (see its docstring): derive + compact
            this span's pairs → ([C] centers, [C] contexts, count)."""
            return _pack_span(ids, sent, n_valid, p0, S, W, C, kb)

        shard_axis = (self.table_sharding_axis if self.mesh is not None
                      else None)

        def block_fn(syn0, syn1, ids, sent, n_valid, negpool, p0, lr01, key,
                     blk_id):
            key = jax.random.fold_in(key, blk_id)
            packed_c, packed_x, count = pack(ids, sent, n_valid, p0, key)
            lr0, lr1 = lr01
            countf = jnp.maximum(count.astype(jnp.float32), 1.0)

            def cond(st):
                return st[0] * B < count

            def body(st):
                r, s0, s1, lsum, wsum = st
                c = lax.dynamic_slice(packed_c, (r * B,), (B,))
                x = lax.dynamic_slice(packed_x, (r * B,), (B,))
                pm = ((lax.broadcasted_iota(jnp.int32, (B,), 0) + r * B)
                      < count).astype(jnp.float32)
                # linear LR interpolation across the dispatch (reference
                # updates alpha every 10k words — same granularity class)
                lr = lr0 + (lr1 - lr0) * (r * B).astype(jnp.float32) / countf
                if is_hs:
                    s0, s1, loss = E.skipgram_hs(
                        s0, s1, c, points_d[x], codes_d[x], mask_d[x],
                        lr, pm, dense=False)
                else:
                    negs = _pool_negs(negpool, blk_id, r, B, K, V, x)
                    tgt = jnp.concatenate([x[:, None], negs], axis=1)
                    if shard_axis is not None:
                        s0, s1, loss = E.sharded_skipgram(
                            s0, s1, c, tgt, lab, lr, pm, axis=shard_axis)
                    else:
                        s0, s1, loss = E.skipgram(s0, s1, c, tgt, lab, lr,
                                                  pm, dense=False)
                return (r + 1, s0, s1, lsum + loss * pm.sum(),
                        wsum + pm.sum())

            init = (jnp.int32(0), syn0, syn1, jnp.float32(0.0),
                    jnp.float32(0.0))
            _, syn0, syn1, lsum, wsum = lax.while_loop(cond, body, init)
            return (syn0, syn1, lsum / jnp.maximum(wsum, 1.0), wsum)

        if shard_axis is None:
            return xprof.register_jit(
                "nlp/w2v_table_block",
                jax.jit(block_fn, donate_argnums=(0, 1)), donate=(0, 1))
        # sharded tables: the pack + negatives run REPLICATED (all inputs
        # replicated, deterministic ops), only table rows live split
        from jax.experimental.shard_map import shard_map
        from jax.sharding import PartitionSpec as P

        tspec = P(shard_axis, None)
        sharded = shard_map(
            block_fn, mesh=self.mesh,
            in_specs=(tspec, tspec, P(), P(), P(), P(), P(), P(), P(), P()),
            out_specs=(tspec, tspec, P(), P()),
            check_rep=False)
        return xprof.register_jit(
            "nlp/w2v_table_block",
            jax.jit(sharded, donate_argnums=(0, 1)), donate=(0, 1))

    @property
    def _cbow_centers(self) -> int:
        """Examples per device-windowed CBOW round (same tiny-vocab
        stability cap rationale as ``_round_pairs``; same HS root-row
        collision cap)."""
        cap = min(self.batch_size, 8 * max(len(self.vocab), 1))
        if self.use_hs:
            cap = min(cap, self.HS_MAX_ROUND)
        return max(1, cap)

    # -- shared device-window helpers (skip-gram + CBOW blocks) ----------
    def _build_negpool(self, ntable_dev, round_negs: int):
        """Pre-drawn negative pool (see _make_window_block docstring);
        shared by both windowed blocks so the stride/seed/size contracts
        cannot drift between algorithms."""
        import jax
        import jax.numpy as jnp

        if round_negs >= self.NEG_POOL_SIZE:
            raise ValueError(
                f"negatives per round ({round_negs}) must be below "
                f"NEG_POOL_SIZE={self.NEG_POOL_SIZE}; lower batch_size/"
                "negative or raise NEG_POOL_SIZE")
        T = ntable_dev.shape[0]
        kp = jax.random.PRNGKey((self.seed ^ 0x5DEECE66) & 0x7FFFFFFF)
        bits = jax.random.bits(kp, (self.NEG_POOL_SIZE,), jnp.uint32)
        return ntable_dev[(bits & (T - 1)).astype(jnp.int32)]

    def _make_cbow_window_block(self, hs_dev=None, ntable_dev=None):
        """Device-windowed CBOW block (round-4): the corpus lives on
        device and every dispatch derives a span of S = B_C·R center
        positions' context windows there — contexts from 2W shifted
        slices, masked mean in the kernel. Unlike skip-gram there is
        nothing to compact: every in-bounds position IS one example, so a
        plain fixed-R ``lax.scan`` is already dense (examples whose
        reduced window is empty carry pair-mask 0). Negatives ride the
        same pre-drawn pool as the skip-gram block. Statistical procedure
        matches the host CBOW path (reduced windows, masked mean,
        NS/HS on the center word)."""
        import functools

        import jax
        import jax.numpy as jnp
        from jax import lax

        from ..ops import embeddings as E

        is_hs = self.use_hs
        V, K, W = len(self.vocab), self.negative, self.window
        B_C = self._cbow_centers
        R = self.MAX_BLOCK_ROUNDS
        S = B_C * R
        if is_hs:
            points_d, codes_d, mask_d = hs_dev
            self._win_negpool = jnp.zeros((8,), jnp.int32)
        else:
            lab = jnp.zeros((B_C, 1 + K), jnp.float32).at[:, 0].set(1.0)
            self._win_negpool = self._build_negpool(ntable_dev, B_C * K)

        @functools.partial(jax.jit, donate_argnums=(0, 1))
        def block(syn0, syn1, ids, sent, n_valid, negpool, p0, lr01, key,
                  blk_id):
            key = jax.random.fold_in(key, blk_id)
            c_ids, ctx_all, valid, live = _derive_windows(
                ids, sent, n_valid, p0, S, W, key)
            cm_all = valid.astype(jnp.float32)
            lr0, lr1 = lr01

            def body(carry, r):
                s0, s1 = carry
                sl = r * B_C
                c = lax.dynamic_slice(c_ids, (sl,), (B_C,))
                cx = lax.dynamic_slice(ctx_all, (sl, jnp.int32(0)),
                                       (B_C, 2 * W))
                cm = lax.dynamic_slice(cm_all, (sl, jnp.int32(0)),
                                       (B_C, 2 * W))
                lv = lax.dynamic_slice(live, (sl,), (B_C,))
                pm = (lv & (cm.sum(axis=1) > 0)).astype(jnp.float32)
                lr = lr0 + (lr1 - lr0) * r.astype(jnp.float32) / R
                if is_hs:
                    s0, s1, loss = E.cbow_hs(
                        s0, s1, cx, cm, points_d[c], codes_d[c],
                        mask_d[c], lr, pm, dense=False)
                else:
                    negs = _pool_negs(negpool, blk_id, r, B_C, K, V, c)
                    tgt = jnp.concatenate([c[:, None], negs], axis=1)
                    s0, s1, loss = E.cbow(s0, s1, cx, cm, tgt, lab, lr,
                                          pm, dense=False)
                return (s0, s1), (loss, pm.sum())

            (syn0, syn1), (losses, ns) = lax.scan(
                body, (syn0, syn1), jnp.arange(R, dtype=jnp.int32))
            return (syn0, syn1,
                    (losses * ns).sum() / jnp.maximum(ns.sum(), 1.0),
                    ns.sum())

        return xprof.register_jit("nlp/w2v_cbow_block", block,
                                  donate=(0, 1))

    def _block_for(self, tag: str, make: Callable, *extra):
        """Shared block-function cache: rebuild (re-trace) only when the
        config/vocab the closure captures actually changed. ``make``
        receives ``(hs_dev, ntable_dev)`` device tables. Keyed BY TAG so
        paths that alternate two blocks in one fit (ParagraphVectors DBOW
        + word skip-gram) don't thrash a single slot."""
        import jax.numpy as jnp

        # content hash (not just len/sum): two rebuilt vocabs with equal size
        # and total count must not reuse stale Huffman paths / unigram tables
        counts = np.ascontiguousarray(self.vocab.counts())
        key = (len(self.vocab), hash(counts.tobytes()),
               self.negative, self.algorithm, self.use_hs) + extra
        cache = getattr(self, "_block_cache", None)
        if cache is None:
            cache = self._block_cache = {}
        if tag not in cache or cache[tag][0] != key:
            hs_dev = ntable_dev = None
            if self.use_hs:
                hs_codes, hs_points, hs_mask = huffman_arrays(self.vocab)
                hs_dev = (jnp.asarray(hs_points), jnp.asarray(hs_codes),
                          jnp.asarray(hs_mask))
            else:
                ntable_dev = jnp.asarray(unigram_int_table(self.vocab))
            cache[tag] = (key, make(hs_dev, ntable_dev))
        return cache[tag][1]

    def _train_windowed(self, corpus: List[np.ndarray],
                        total_words: Optional[int] = None) -> None:
        """Device-resident-corpus fit for BOTH algorithms: skip-gram
        (``_make_window_block``, dense-packed pairs) and CBOW
        (``_make_cbow_window_block``, one example per position).
        Statistical procedure matches the host path: frequent-word
        subsampling + stream compaction per epoch (ON DEVICE since round
        4 — ``_subsample_fn``, keyed off a dedicated fold of the base
        key), reduced windows, NS from the unigram^0.75 pool or HS
        Huffman paths, linear LR decay by corpus-words consumed."""
        import jax
        import jax.numpy as jnp

        keep = subsample_keep_probs(self.vocab, self.sampling)
        raw_words = sum(len(s) for s in corpus)
        if total_words is None:
            total_words = raw_words * self.epochs * self.iterations

        is_cbow = self.algorithm == "cbow"
        if is_cbow and self.mesh is not None:
            raise ValueError("sharded tables support the skip-gram "
                             "windowed path only (no sharded CBOW kernel)")
        if is_cbow:
            block = self._block_for("cwin", self._make_cbow_window_block,
                                    self.window, self._cbow_centers)
        else:
            block = self._block_for("win", self._make_window_block,
                                    self.window, self._window_centers,
                                    None if self.mesh is None
                                    else (id(self.mesh),
                                          self.table_sharding_axis))

        flat = (np.concatenate(corpus) if corpus
                else np.empty(0, np.int32)).astype(np.int32)
        lens = np.array([c.size for c in corpus], dtype=np.int64)
        # Sentence ids travel as uint16 via mod-65535: the boundary check
        # only compares positions ≤ W apart, whose true sentence ids differ
        # by ≤ W < 65535, so modular equality is EXACT. 65535 is the pad
        # sentinel (never a real id), making boundary checks fail in the
        # pad region.
        assert self.window < 65535
        sent_full = (np.repeat(np.arange(len(corpus), dtype=np.int64), lens)
                     % 65535).astype(np.uint16)
        idx_dt = (np.uint16 if len(self.vocab) <= (1 << 16) else np.int32)
        sent_dt = np.uint16

        base_key = jax.random.PRNGKey(self.seed)
        tdt = (jnp.bfloat16 if getattr(self, "table_dtype", "float32")
               == "bfloat16" else jnp.float32)
        syn1_host = (self.lookup_table.syn1 if self.use_hs
                     else self.lookup_table.syn1neg)
        V = len(self.vocab)
        if self.mesh is not None:
            # row-shard the tables over the mesh axis (zero-padded to a
            # shard multiple; pad rows are unreachable — ids < V)
            from jax.sharding import NamedSharding, PartitionSpec as P

            n_sh = self.mesh.shape[self.table_sharding_axis]
            Vp = -(-V // n_sh) * n_sh
            tsh = NamedSharding(self.mesh, P(self.table_sharding_axis,
                                             None))
            self._repl_sharding = NamedSharding(self.mesh, P())

            def place(t):
                padded = np.zeros((Vp, t.shape[1]), np.float32)
                padded[:V] = np.asarray(t)
                return jax.device_put(jnp.asarray(padded, tdt), tsh)

            syn0, syn1 = place(self.lookup_table.syn0), place(syn1_host)
        else:
            self._repl_sharding = None
            syn0 = jnp.asarray(self.lookup_table.syn0, tdt)
            syn1 = jnp.asarray(syn1_host, tdt)
        losses, pair_counts = [], []
        n_blocks = 0
        words_seen = 0
        t0 = time.perf_counter()

        # --- corpus → device, ONCE per distinct corpus (cached across
        # fits: the bench/resume pattern re-fits the same corpus).
        # Frequent-word subsampling then runs ON DEVICE each epoch, so the
        # host-subsampled stream (~4 bytes/word/epoch) is not re-uploaded
        # every epoch. Chosen on a set-up that is gone; not measured on
        # this chip.
        # Layout: [W sentinel front-pad][stream][sentinel tail] — the
        # front pad lets the pack derive windows from shifted slices.
        W = self.window
        npad = -(-max(flat.size, 1) // self.CORPUS_BUCKET) \
            * self.CORPUS_BUCKET
        span = (self._cbow_centers * self.MAX_BLOCK_ROUNDS if is_cbow
                else self._window_span)   # positions per dispatch
        buf_len = npad + span + 2 * W
        ckey = (flat.size, hash(flat.tobytes()), buf_len, str(idx_dt),
                None if self.mesh is None else id(self.mesh))
        cached = getattr(self, "_corpus_dev_cache", None)
        if cached is not None and cached[0] == ckey:
            ids_full, sent_full_dev = cached[1]
        else:
            ids_np = np.zeros(buf_len, idx_dt)
            ids_np[W:W + flat.size] = flat.astype(idx_dt)
            sent_np = np.full(buf_len, np.iinfo(sent_dt).max, sent_dt)
            sent_np[W:W + flat.size] = sent_full
            ids_full = jax.device_put(ids_np, self._repl_sharding)
            sent_full_dev = jax.device_put(sent_np, self._repl_sharding)
            self._corpus_dev_cache = (ckey, (ids_full, sent_full_dev))
        if self.mesh is not None:
            self._win_negpool = jax.device_put(self._win_negpool,
                                               self._repl_sharding)
        n_raw = flat.size

        if self.sampling > 0:
            keep_dev = jnp.asarray(keep.astype(np.float32))
            subsample = self._subsample_fn()
            ksub_base = jax.random.fold_in(base_key, (1 << 31) - 1)
            # Host-side expectations pace the LR and bound the dispatch
            # loop WITHOUT reading the device count back (no sync): the
            # realized count exceeds E+6σ with probability ~1e-9 (binomial
            # tail); the sub-span tail beyond the bound would lose <1e-5
            # of one epoch's positions even then.
            kf = keep[flat]
            n_exp = float(kf.sum())
            n_loop = min(n_raw, int(n_exp + 6.0 * np.sqrt(
                max(float((kf * (1.0 - kf)).sum()), 1.0)) + 1))
        else:
            n_exp = float(n_raw)
            n_loop = n_raw

        def lr_at(frac: float) -> np.float32:
            return np.float32(max(
                self.learning_rate * (1.0 - min(frac, 1.0)),
                self.min_learning_rate))

        for _epoch in range(self.epochs):
            if self.sampling > 0:
                ids_dev, sent_dev, n_valid = subsample(
                    ids_full, sent_full_dev, keep_dev, np.int32(n_raw),
                    jax.random.fold_in(ksub_base, _epoch))
            else:
                ids_dev, sent_dev = ids_full, sent_full_dev
                n_valid = np.int32(n_raw)
            for _it in range(self.iterations):
                it_base = words_seen
                for p0 in range(0, n_loop, span):
                    # LR decays by raw corpus words consumed; compacted
                    # position p maps to ~p/n_exp of this epoch-pass's
                    # words. The block interpolates linearly between the
                    # span's start/end rates on device.
                    lr0 = lr_at((it_base + p0 / max(n_exp, 1.0) * raw_words)
                                / max(total_words, 1))
                    lr1 = lr_at((it_base
                                 + min(p0 + span, n_loop) / max(n_exp, 1.0)
                                 * raw_words) / max(total_words, 1))
                    syn0, syn1, loss, np_ = block(
                        syn0, syn1, ids_dev, sent_dev, n_valid,
                        self._win_negpool, np.int32(p0), (lr0, lr1),
                        base_key, np.int32(n_blocks))
                    n_blocks += 1
                    losses.append(loss)
                    pair_counts.append(np_)
                words_seen += raw_words
        # VALUE fence (see _train_encoded): read back results that depend
        # on the full chain, once.
        last = (np.asarray(jnp.stack(losses[-50:])) if losses
                else np.zeros(1, np.float32))
        pairs_seen = (float(np.asarray(jnp.stack(pair_counts)).sum())
                      if pair_counts else 0.0)
        dt = time.perf_counter() - t0
        self.words_per_sec = words_seen / max(dt, 1e-9)
        self.pairs_per_sec = pairs_seen / max(dt, 1e-9)
        self.last_loss = float(last.mean()) if losses else 0.0
        # strip to the TABLE's row count: drops the shard-padding rows of
        # a mesh-sharded fit, but keeps FastText's n-gram bucket rows
        # (lookup_table.vocab_size = V + bucket there)
        n_rows = self.lookup_table.vocab_size or len(self.vocab)
        self.lookup_table.syn0 = np.asarray(syn0.astype(jnp.float32))[:n_rows]
        if self.use_hs:
            self.lookup_table.syn1 = np.asarray(
                syn1.astype(jnp.float32))[:n_rows]
        else:
            self.lookup_table.syn1neg = np.asarray(
                syn1.astype(jnp.float32))[:n_rows]

    def _train_encoded(self, corpus: List[np.ndarray],
                       stream_factory: Optional[Callable] = None,
                       total_words: Optional[int] = None) -> None:
        """Run the full fit over an encoded corpus.

        ``stream_factory(rng, keep)`` (optional) overrides per-sentence batch
        generation — it must yield ``(centers, contexts)`` tuples for
        skip-gram configs or ``(centers, ctx, cmask)`` for CBOW configs.
        ParagraphVectors uses this to inject doc-label ids into the stream.

        Plain fits (no custom stream) — skip-gram AND CBOW — use the
        device-windowed path (``_train_windowed``): corpus resident on
        device, windows derived there. Custom streams (ParagraphVectors)
        use the host pair pipeline below (native ``sg_pairs`` C++ producer
        + background staging); ``device_corpus=False`` on the instance
        forces the host path for either algorithm.
        """
        import jax.numpy as jnp

        import jax

        if (stream_factory is None
                and getattr(self, "device_corpus", True)):
            # both algorithms ride the device-windowed corpus (round 4:
            # CBOW derives its windows on device too)
            return self._train_windowed(corpus, total_words)
        if getattr(self, "mesh", None) is not None:
            raise ValueError(
                "sharded tables (mesh=...) are implemented for the "
                "device-windowed paths only — custom streams "
                "(ParagraphVectors) and device_corpus=False would "
                "silently train unsharded")

        rng = np.random.default_rng(self.seed)
        keep = subsample_keep_probs(self.vocab, self.sampling)
        block = self._block_for("host", self._make_block, self.batch_size)
        base_key = jax.random.PRNGKey(self.seed)
        n_blocks = 0
        V = len(self.vocab)
        B, K = self.batch_size, self.negative
        if total_words is None:
            total_words = (sum(len(s) for s in corpus)
                           * self.epochs * self.iterations)
        syn0 = jnp.asarray(self.lookup_table.syn0)
        syn1 = jnp.asarray(self.lookup_table.syn1 if self.use_hs
                           else self.lookup_table.syn1neg)

        is_cbow = self.algorithm == "cbow"
        words_seen = 0     # corpus words consumed (drives the LR schedule)
        pairs_seen = 0     # training examples executed on device
        losses = []
        t0 = time.perf_counter()

        def _lr() -> np.float32:
            # Linear decay by CORPUS WORDS CONSUMED (word2vec.c semantics:
            # alpha decays with corpus progress, not with pair count).
            frac = min(words_seen / max(total_words, 1), 1.0)
            return np.float32(max(self.learning_rate * (1 - frac),
                                  self.min_learning_rate))

        # uint16 indices on the wire whenever the TABLE fits (fewer
        # host→device bytes — see _make_block). The
        # table can be taller than the vocab: FastText streams subword row
        # ids up to V + bucket, so sizing off len(vocab) alone would wrap
        # ids >= 2^16.
        n_rows = self.lookup_table.vocab_size or V
        idx_dt = np.uint16 if n_rows <= (1 << 16) else np.int32

        def _rounds(npairs):
            """Pad-to-a-multiple-of-a-full-block bookkeeping shared by
            both flushes. Padded pairs are masked out on DEVICE from the
            per-round valid count ``nv``."""
            pad = (-npairs) % (B * self.MAX_BLOCK_ROUNDS)
            R = (npairs + pad) // B
            nv = np.minimum(np.maximum(npairs - np.arange(R) * B, 0),
                            B).astype(np.int32)
            return pad, nv, R

        def _blocks(R):
            """Split R rounds (a multiple of MAX_BLOCK_ROUNDS) into
            full-sized scanned blocks — ONE compiled shape per fit."""
            for r in range(0, R, self.MAX_BLOCK_ROUNDS):
                yield r, self.MAX_BLOCK_ROUNDS

        def _stage(cols):
            """Upload a block's columns from the PRODUCER thread so H2D
            transfer overlaps the consumer's device dispatches."""
            return tuple(jax.device_put(a) for a in cols)

        def flush_sg(centers, contexts):
            nonlocal pairs_seen
            npairs = centers.size
            pad, nv, R = _rounds(npairs)
            c3 = np.pad(centers.astype(idx_dt), (0, pad)).reshape(R, B)
            x3 = np.pad(contexts.astype(idx_dt), (0, pad)).reshape(R, B)
            lr = _lr()
            pairs_seen += npairs
            for r, nb in _blocks(R):
                sl = slice(r, r + nb)
                yield _stage((c3[sl], x3[sl], nv[sl],
                              np.full(nb, lr, np.float32)))

        def flush_cbow(centers, ctx, cmask):
            nonlocal pairs_seen
            npairs = centers.size
            pad, nv, R = _rounds(npairs)
            W = ctx.shape[1]
            c3 = np.pad(centers.astype(idx_dt), (0, pad)).reshape(R, B)
            ctx3 = np.pad(ctx.astype(idx_dt),
                          ((0, pad), (0, 0))).reshape(R, B, W)
            cm3 = np.pad(cmask.astype(np.uint8),
                         ((0, pad), (0, 0))).reshape(R, B, W)
            lr = _lr()
            pairs_seen += npairs
            for r, nb in _blocks(R):
                sl = slice(r, r + nb)
                yield _stage((ctx3[sl], cm3[sl], c3[sl], nv[sl],
                              np.full(nb, lr, np.float32)))

        def default_stream(rng, keep):
            if is_cbow:
                for ids in corpus:
                    wins = self._sentence_windows(ids, rng, keep)
                    if wins is not None:
                        yield (ids.size,) + wins
                return
            # skip-gram pair generation: one native call per sentence chunk
            # (libdatavec_native, SURVEY §7.1.2 "native where the reference
            # is native") with the numpy per-sentence path as fallback
            from .. import native

            if native.available():
                CHUNK = 2048
                keep_arr = keep if self.sampling > 0 else None
                for s0 in range(0, len(corpus), CHUNK):
                    chunk = corpus[s0:s0 + CHUNK]
                    offsets = np.zeros(len(chunk) + 1, np.int64)
                    np.cumsum([c.size for c in chunk], out=offsets[1:])
                    flat = np.concatenate(chunk) if chunk else \
                        np.empty(0, np.int32)
                    c, x = native.sg_pairs(
                        flat, offsets, self.window, keep_arr,
                        int(rng.integers(1, 2 ** 63 - 1)))
                    if c.size:
                        yield int(offsets[-1]), c, x
                return
            for ids in corpus:
                pairs = self._sentence_pairs(ids, rng, keep)
                if pairs is not None:
                    yield (ids.size,) + pairs

        if stream_factory is None:
            stream_factory = default_stream

        def work_items():
            """Producer generator: pair generation + batching + padding on
            the host, yielding ready column blocks. Runs on a background
            thread (``prefetch_iter``) so pair-gen for flush N+1 overlaps
            the device executing flush N — the TPU analog of the
            reference's N worker threads keeping the JNI kernels fed."""
            nonlocal words_seen
            # Mid-fit flushes emit only FULL MAX_BLOCK_ROUNDS blocks and
            # carry the remainder pairs forward (even across epochs): tail
            # blocks pay upload fixed-costs out of proportion to their
            # size, so exactly one padded tail runs — at the very end.
            chunk = self.MAX_BLOCK_ROUNDS * B
            if is_cbow:
                buf = []
                buffered = 0
                for _epoch in range(self.epochs):
                    for item in stream_factory(rng, keep):
                        nwords, wins = item[0], item[1:]
                        words_seen += nwords * self.iterations
                        for _ in range(self.iterations):
                            buf.append(wins)
                            buffered += wins[0].size
                        if buffered >= chunk:
                            c, ctx, cm = (np.concatenate([w[i] for w in buf])
                                          for i in range(3))
                            n_full = (c.shape[0] // chunk) * chunk
                            yield from flush_cbow(c[:n_full], ctx[:n_full],
                                                  cm[:n_full])
                            buf = [(c[n_full:], ctx[n_full:], cm[n_full:])]
                            buffered = c.shape[0] - n_full
                if buffered:
                    yield from flush_cbow(
                        np.concatenate([w[0] for w in buf]),
                        np.concatenate([w[1] for w in buf]),
                        np.concatenate([w[2] for w in buf]))
            else:
                buf_c: List[np.ndarray] = []
                buf_x: List[np.ndarray] = []
                buffered = 0
                for _epoch in range(self.epochs):
                    for item in stream_factory(rng, keep):
                        nwords, pairs = item[0], item[1:]
                        words_seen += nwords * self.iterations
                        for _ in range(self.iterations):
                            buf_c.append(pairs[0])
                            buf_x.append(pairs[1])
                            buffered += pairs[0].size
                        if buffered >= chunk:
                            c = np.concatenate(buf_c)
                            x = np.concatenate(buf_x)
                            n_full = (c.size // chunk) * chunk
                            yield from flush_sg(c[:n_full], x[:n_full])
                            buf_c, buf_x = [c[n_full:]], [x[n_full:]]
                            buffered = c.size - n_full
                if buffered:
                    yield from flush_sg(np.concatenate(buf_c),
                                        np.concatenate(buf_x))

        from ..common.background import prefetch_iter

        for cols in prefetch_iter(work_items(), maxsize=8):
            syn0, syn1, loss = block(syn0, syn1, cols, base_key,
                                     np.int32(n_blocks))
            n_blocks += 1
            losses.append(loss)   # device scalar; no sync in the loop

        # VALUE fence: reading back a value that depends on the whole
        # chain is a barrier that cannot return early. One stacked
        # readback also replaces the 50 per-scalar syncs the loss average
        # used to pay.
        last = (np.asarray(jnp.stack(losses[-50:])) if losses
                else np.zeros(1, np.float32))
        dt = time.perf_counter() - t0
        self.words_per_sec = words_seen / max(dt, 1e-9)
        self.pairs_per_sec = pairs_seen / max(dt, 1e-9)
        self.last_loss = float(last.mean()) if losses else 0.0
        self.lookup_table.syn0 = np.asarray(syn0)
        if self.use_hs:
            self.lookup_table.syn1 = np.asarray(syn1)
        else:
            self.lookup_table.syn1neg = np.asarray(syn1)

    @staticmethod
    def _neg_targets(pos: np.ndarray, rng: np.random.Generator,
                     cdf: np.ndarray, V: int, K: int):
        """[B, 1+K] targets (col 0 = positive) + labels; negatives drawn
        from the unigram^0.75 CDF, collisions with the positive shifted by
        one (the reference resamples; a deterministic shift is unbiased to
        O(1/V) and keeps the host path branch-free)."""
        B = pos.shape[0]
        negs = np.searchsorted(cdf, rng.random((B, K))).astype(np.int32)
        negs = np.where(negs == pos[:, None], (negs + 1) % V, negs)
        targets = np.concatenate([pos[:, None], negs], axis=1)
        labels = np.zeros((B, 1 + K), dtype=np.float32)
        labels[:, 0] = 1.0
        return targets, labels


def _derive_windows(ids, sent, n_valid, p0, S, W, key):
    """Shared device window derivation for the windowed blocks: one
    contiguous dynamic-slice window (buffers carry W front-pad sentinel
    slots; stream position p = buffer index p+W), contexts as 2W STATIC
    shifted slices, validity from reduced window b ~ U[1, W] + sentence
    equality + stream bounds. Returns (c_ids [S], ctx [S, 2W],
    valid [S, 2W] bool, live [S] bool)."""
    import jax
    import jax.numpy as jnp
    from jax import lax

    idw = lax.dynamic_slice(ids, (p0,), (S + 2 * W,)).astype(jnp.int32)
    sw = lax.dynamic_slice(sent, (p0,), (S + 2 * W,)).astype(jnp.int32)
    c_ids = idw[W:W + S]
    c_sent = sw[W:W + S]
    p = p0 + lax.broadcasted_iota(jnp.int32, (S,), 0)
    live = p < n_valid
    b = jax.random.randint(key, (S,), 1, W + 1)
    ctx_cols, v_cols = [], []
    for o in list(range(-W, 0)) + list(range(1, W + 1)):
        ctx_cols.append(idw[W + o:W + o + S])
        v_cols.append((b >= abs(o)) & live
                      & (sw[W + o:W + o + S] == c_sent))
    return (c_ids, jnp.stack(ctx_cols, 1), jnp.stack(v_cols, 1), live)


def _pack_span(ids, sent, n_valid, p0, S, W, C, key):
    """Derive + densely compact a span's skip-gram pairs → ([C] centers,
    [C] contexts, count). Window derivation is the shared
    ``_derive_windows`` (shifted slices — the round-3 element-granular
    ids[q] gathers were the single most expensive fusion in the device
    trace). Compaction is an order-preserving cumsum→scatter, so pairs
    train in corpus order. Shared by the skip-gram windowed block and
    FastText's subword block."""
    import jax.numpy as jnp

    c_ids, x_ids, valid, _ = _derive_windows(ids, sent, n_valid, p0, S, W,
                                             key)
    vf = valid.reshape(-1)
    dest = jnp.cumsum(vf.astype(jnp.int32)) - 1
    count = jnp.minimum(dest[-1] + 1, C)
    slot = jnp.where(vf, dest, C)               # C = dropped
    packed_c = jnp.zeros((C,), jnp.int32).at[slot].set(
        jnp.broadcast_to(c_ids[:, None], (S, 2 * W)).reshape(-1),
        mode="drop")
    packed_x = jnp.zeros((C,), jnp.int32).at[slot].set(
        x_ids.reshape(-1), mode="drop")
    return packed_c, packed_x, count


def _pool_negs(negpool, blk_id, r, B, K, V, positives):
    """Stride-walk a [B, K] window of the pre-drawn pool for round ``r``
    of dispatch ``blk_id`` and collision-shift against ``positives``
    (rounds per dispatch < 131; uint32 math so the product wraps safely)."""
    import jax.numpy as jnp
    from jax import lax

    g = blk_id.astype(jnp.uint32) * jnp.uint32(131) + r.astype(jnp.uint32)
    start = ((g * jnp.uint32(48611))
             % jnp.uint32(negpool.shape[0] - B * K)).astype(jnp.int32)
    negs = lax.dynamic_slice(negpool, (start,), (B * K,)).reshape(B, K)
    return jnp.where(negs == positives[:, None], (negs + 1) % V, negs)


class Word2Vec(SequenceVectors):
    """Word2Vec over a sentence corpus (reference: Word2Vec.Builder →
    SequenceVectors.fit, SURVEY §3.6)."""

    class Builder:
        def __init__(self) -> None:
            self._kw = {}
            self._iter: Optional[SentenceIterator] = None
            self._tok: TokenizerFactory = DefaultTokenizerFactory()

        def min_word_frequency(self, v): self._kw["min_word_frequency"] = v; return self
        def iterations(self, v): self._kw["iterations"] = v; return self
        def epochs(self, v): self._kw["epochs"] = v; return self
        def layer_size(self, v): self._kw["layer_size"] = v; return self
        def seed(self, v): self._kw["seed"] = v; return self
        def window_size(self, v): self._kw["window"] = v; return self
        def learning_rate(self, v): self._kw["learning_rate"] = v; return self
        def min_learning_rate(self, v): self._kw["min_learning_rate"] = v; return self
        def negative_sample(self, v): self._kw["negative"] = int(v); return self
        def use_hierarchic_softmax(self, v): self._kw["use_hierarchic_softmax"] = v; return self
        def sampling(self, v): self._kw["sampling"] = v; return self
        def batch_size(self, v): self._kw["batch_size"] = v; return self
        def workers(self, v): self._kw["workers"] = v; return self
        def table_dtype(self, v): self._kw["table_dtype"] = v; return self

        def sharded_tables(self, mesh, axis: str = "model"):
            """Row-shard syn0/syn1 over a mesh axis (the reference's
            VoidParameterServer workload, run as compiled collectives)."""
            self._kw["mesh"] = mesh
            self._kw["table_sharding_axis"] = axis
            return self

        def elements_learning_algorithm(self, name: str):
            self._kw["algorithm"] = \
                "cbow" if "cbow" in name.lower() else "skipgram"
            return self

        def iterate(self, it):
            if isinstance(it, (list, tuple)):
                it = CollectionSentenceIterator(it)
            self._iter = it
            return self

        def tokenizer_factory(self, tf: TokenizerFactory):
            self._tok = tf
            return self

        def build(self) -> "Word2Vec":
            w2v = Word2Vec(**self._kw)
            w2v._sentence_iter = self._iter
            w2v._tokenizer = self._tok
            return w2v

    @staticmethod
    def builder() -> "Word2Vec.Builder":
        return Word2Vec.Builder()

    def __init__(self, **kw):
        super().__init__(**kw)
        self._sentence_iter: Optional[SentenceIterator] = None
        self._tokenizer: TokenizerFactory = DefaultTokenizerFactory()

    def set_sentence_iterator(self, it) -> None:
        if isinstance(it, (list, tuple)):
            it = CollectionSentenceIterator(it)
        self._sentence_iter = it

    def _token_stream(self):
        assert self._sentence_iter is not None, \
            "no corpus: call iterate()/set_sentence_iterator first"
        self._sentence_iter.reset()
        for sentence in self._sentence_iter:
            yield self._tokenizer.create(sentence).get_tokens()

    def fit(self) -> None:
        """Train. First call builds the vocab and initializes tables; a
        model that already has vocab + tables (a second ``fit`` or one
        restored by ``read_word2vec_model``) RESUMES training with the
        existing state — corpus words outside the stored vocab are
        dropped."""
        if len(self.vocab) == 0 or self.lookup_table.syn0 is None:
            self.build_vocab(self._token_stream())
            if len(self.vocab) == 0:
                raise ValueError("empty vocabulary after pruning — lower "
                                 "min_word_frequency or supply more text")
        corpus = self._encode_corpus(self._token_stream())
        self._train_encoded(corpus)
