"""Fused embedding-training rounds: skip-gram / CBOW, NS + HS.

TPU-native rebuild of the reference's fused word2vec kernels (reference:
libnd4j ``ops/declarable/helpers/cpu/sg_cb.cpp`` — ``skipgram``/``cbow``
declarable ops doing fused dot/sigmoid/axpy over syn0/syn1 rows, dispatched
per center/context pair over JNI).

The TPU formulation inverts the granularity: instead of one kernel launch per
training pair, a whole BATCH of pairs becomes one jitted XLA module —
gather rows → batched dot → sigmoid → scaled error → accumulate back into
the tables. All rounds return ``(syn0', syn1', loss)``; callers jit with
``donate_argnums=(0, 1)`` so the tables update in place on device.

Table accumulation has two lowerings, selected by the static ``dense`` flag:

- ``dense=False`` (the production path): XLA scatter-add
  (``Array.at[idx].add``) — deterministic, sums duplicate indices exactly
  like the reference's serialized per-pair axpy, and touches ONLY the
  sampled rows (the reference sg_cb's O(batch·D) shape). Round-3
  re-measurement with value-fenced rep-differencing
  (``tools/w2v_update_bench.py`` on v5e): 326M rows/s at V=10k, 74M rows/s
  at V=100k — the earlier "per-row serialized ~100–200k rows/s" claim was
  a broken-fence artifact of the round-1 methodology.
- ``dense=True``: the update becomes ``onehot(idx)ᵀ @ grads`` — a bf16 MXU
  matmul accumulated into the f32 table. O(batch·V) one-hot HBM traffic
  makes it 8–16× SLOWER than scatter at every vocab measured (9.9k–100k);
  kept for MXU experiments and as a numerical cross-check in tests, never
  auto-selected.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from .registry import op

# Vocab threshold below which SequenceVectors picks the dense one-hot MXU
# update. Round-3 measurement (module docstring) shows scatter wins at every
# size, so the threshold is 0 = never dense; the knob survives so the
# shootout in tools/w2v_update_bench.py can keep regression-checking it.
DENSE_UPDATE_MAX_ROWS = 0


def _table_add(table, idx, grads, dense: bool):
    """table[idx] += grads with the scatter or MXU-matmul lowering.

    idx [N] int32, grads [N, D]. Duplicate indices sum in both paths.
    """
    if dense:
        onehot = jax.nn.one_hot(idx, table.shape[0], dtype=jnp.bfloat16)
        return table + jnp.einsum(
            "nv,nd->vd", onehot, grads.astype(jnp.bfloat16),
            preferred_element_type=table.dtype)
    # grads may be f32 even when the table is bf16 (the NS/HS math promotes
    # through the f32 labels/lr); cast so the scatter writes table-width
    return table.at[idx].add(grads.astype(table.dtype))


@op("embedding_bag", "nlp")
def embedding_bag(table, indices, mask=None, mode: str = "mean"):
    """Pooled embedding lookup: ``table [V, D]``, ``indices [B, W]``,
    optional ``mask [B, W]`` (0 = pad) → ``[B, D]`` masked mean/sum of
    the gathered rows — the CBOW window pooling and the
    ``EmbeddingSequenceLayer``-style bag in one op. One XLA lowering on
    every platform, BITWISE the expression the nlp rounds always computed
    (``(table[ix] * mask).sum(1) / counts``)."""
    if mode not in ("mean", "sum"):
        raise ValueError(f"embedding_bag mode {mode!r}")
    if mask is None:
        mask = jnp.ones(indices.shape, table.dtype)
    mask = mask.astype(table.dtype)
    h = (table[indices] * mask[..., None]).sum(axis=1)    # [B, D]
    if mode == "sum":
        return h
    return h / jnp.maximum(mask.sum(axis=1, keepdims=True), 1.0)


def _neg_round(h, u, labels, lr, pair_mask):
    """Shared NS math: h [B,D] vs u [B,K,D], labels [B,K] in {0,1}.

    Returns (grad_h [B,D], grad_u [B,K,D], loss scalar). Gradients are
    ASCENT direction pre-scaled by lr (reference sg_cb applies
    ``g = (label - sigmoid) * alpha`` then axpy)."""
    # The reference evaluates sigmoid through a lookup table clamped to
    # ±MAX_EXP=6 (libnd4j sg_cb expTable); the clamp doubles as its
    # stability mechanism — keep it so batched updates stay bounded.
    logits = jnp.clip(jnp.einsum("bd,bkd->bk", h, u), -6.0, 6.0)
    sig = jax.nn.sigmoid(logits)
    g = (labels - sig) * lr * pair_mask[:, None]          # [B, K]
    grad_h = jnp.einsum("bk,bkd->bd", g, u)
    grad_u = g[..., None] * h[:, None, :]
    # Masked mean binary-XE purely for monitoring (the reference never
    # computes a loss in sg_cb; we surface one for listeners/benches).
    eps = 1e-7
    xe = -(labels * jnp.log(sig + eps) + (1 - labels) * jnp.log(1 - sig + eps))
    denom = jnp.maximum(pair_mask.sum() * labels.shape[1], 1.0)
    loss = (xe * pair_mask[:, None]).sum() / denom
    return grad_h, grad_u, loss


@op("skipgram", "nlp")
def skipgram(syn0, syn1neg, centers, targets, labels, lr, pair_mask,
             dense: bool = False):
    """One negative-sampling skip-gram round over a batch of pairs.

    syn0 [V,D] input vectors; syn1neg [V,D] output vectors;
    centers [B] int32; targets [B,K] int32 (col 0 = true context, rest
    negatives); labels [B,K] float (1 positive / 0 negative);
    lr scalar; pair_mask [B] float zeroing padded pairs.
    """
    h = syn0[centers]                                     # [B, D]
    u = syn1neg[targets]                                  # [B, K, D]
    grad_h, grad_u, loss = _neg_round(h, u, labels, lr, pair_mask)
    d = syn0.shape[1]
    syn0 = _table_add(syn0, centers, grad_h, dense)
    syn1neg = _table_add(syn1neg, targets.reshape(-1),
                         grad_u.reshape(-1, d), dense)
    return syn0, syn1neg, loss


@op("skipgram_hs", "nlp")
def skipgram_hs(syn0, syn1, centers, points, codes, path_mask, lr, pair_mask,
                dense: bool = False):
    """One hierarchical-softmax skip-gram round.

    points/codes/path_mask [B,L]: the context word's padded Huffman path;
    HS label per inner node is ``1 - code`` (word2vec convention the
    reference's hSoftmax path implements).
    """
    h = syn0[centers]
    u = syn1[points]                                      # [B, L, D]
    labels = (1.0 - codes.astype(h.dtype)) * path_mask
    grad_h, grad_u, loss = _neg_round(h, u * path_mask[..., None],
                                      labels, lr, pair_mask)
    grad_u = grad_u * path_mask[..., None]
    d = syn0.shape[1]
    syn0 = _table_add(syn0, centers, grad_h, dense)
    syn1 = _table_add(syn1, points.reshape(-1), grad_u.reshape(-1, d), dense)
    return syn0, syn1, loss


# ---------------------------------------------------------------------------
# Row-sharded variants (the VoidParameterServer workload, SURVEY §2.4 row 4):
# tables live split over a mesh axis inside shard_map; lookups psum the
# masked local gathers (the collective IS the parameter-server round-trip)
# and updates touch only owned rows. Plain functions, not registry ops —
# they only have meaning under a bound mesh axis.


def sharded_local_offsets(table_l, ids, axis: str):
    """Global ids → (clipped local offsets, ownership mask) for a row
    shard [V/N, D] living at this device's position on ``axis``."""
    from jax import lax

    me = lax.axis_index(axis)
    v_local = table_l.shape[0]
    local = ids - me * v_local
    hit = (local >= 0) & (local < v_local)
    return jnp.clip(local, 0, v_local - 1), hit


def sharded_rows_lookup(table_l, ids, axis: str):
    """[B*] global ids → (psum-assembled rows [B*, D], (local, hit)) from a
    row-sharded table shard [V/N, D]."""
    from jax import lax

    local, hit = sharded_local_offsets(table_l, ids, axis)
    rows = table_l[local]
    rows = rows * hit[..., None].astype(rows.dtype)
    return lax.psum(rows, axis), (local, hit)


def sharded_rows_add(table_l, aux, grads):
    """Scatter-add grads into the owned rows only (duplicates sum)."""
    local, hit = aux
    g = grads * hit[..., None].astype(grads.dtype)
    return table_l.at[local].add(g.astype(table_l.dtype))


def sharded_skipgram(syn0_l, syn1_l, centers, targets, labels, lr,
                     pair_mask, axis: str):
    """:func:`skipgram` with syn0/syn1 row-sharded over ``axis`` (call
    inside shard_map). Identical math: the psum-assembled h/u rows make the
    NS round replicated; each shard then applies only its own row updates,
    so the post-round GLOBAL table state equals the single-device round."""
    h, aux_c = sharded_rows_lookup(syn0_l, centers, axis)
    B, K1 = targets.shape
    u_flat, aux_t = sharded_rows_lookup(syn1_l, targets.reshape(-1), axis)
    u = u_flat.reshape(B, K1, -1)
    grad_h, grad_u, loss = _neg_round(h, u, labels, lr, pair_mask)
    d = syn0_l.shape[1]
    syn0_l = sharded_rows_add(syn0_l, aux_c, grad_h)
    syn1_l = sharded_rows_add(syn1_l, aux_t, grad_u.reshape(-1, d))
    return syn0_l, syn1_l, loss


@op("cbow", "nlp")
def cbow(syn0, syn1neg, contexts, ctx_mask, targets, labels, lr, pair_mask,
         dense: bool = False):
    """One negative-sampling CBOW round.

    contexts [B,W] int32 window word ids, ctx_mask [B,W] float (0 = pad);
    h = masked MEAN of context vectors.
    """
    # masked-mean window pooling via the embedding_bag op
    counts = jnp.maximum(ctx_mask.sum(axis=1, keepdims=True), 1.0)
    h = embedding_bag(syn0, contexts, ctx_mask, mode="mean")
    u = syn1neg[targets]
    grad_h, grad_u, loss = _neg_round(h, u, labels, lr, pair_mask)
    d = syn0.shape[1]
    # DOCUMENTED DIVERGENCE from word2vec.c/the reference's CBOW: they apply
    # the full hidden error to EVERY context row, i.e. the true gradient of
    # the mean-forward loss times the window size. Batched accumulation
    # makes that over-scaling unstable (many windows sum into one row per
    # step), so we apply the exact gradient grad_h / |window| instead.
    gctx = (grad_h / counts)[:, None, :] * ctx_mask[..., None]  # [B, W, D]
    syn0 = _table_add(syn0, contexts.reshape(-1), gctx.reshape(-1, d), dense)
    syn1neg = _table_add(syn1neg, targets.reshape(-1),
                         grad_u.reshape(-1, d), dense)
    return syn0, syn1neg, loss


@op("cbow_hs", "nlp")
def cbow_hs(syn0, syn1, contexts, ctx_mask, points, codes, path_mask, lr,
            pair_mask, dense: bool = False):
    """One hierarchical-softmax CBOW round (center word's Huffman path)."""
    counts = jnp.maximum(ctx_mask.sum(axis=1, keepdims=True), 1.0)
    h = embedding_bag(syn0, contexts, ctx_mask, mode="mean")  # masked mean
    u = syn1[points]
    labels = (1.0 - codes.astype(h.dtype)) * path_mask
    grad_h, grad_u, loss = _neg_round(h, u * path_mask[..., None],
                                      labels, lr, pair_mask)
    grad_u = grad_u * path_mask[..., None]
    d = syn0.shape[1]
    # Exact gradient of the mean-forward loss (see cbow's divergence note).
    gctx = (grad_h / counts)[:, None, :] * ctx_mask[..., None]
    syn0 = _table_add(syn0, contexts.reshape(-1), gctx.reshape(-1, d), dense)
    syn1 = _table_add(syn1, points.reshape(-1), grad_u.reshape(-1, d), dense)
    return syn0, syn1, loss
