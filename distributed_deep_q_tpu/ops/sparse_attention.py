"""Learned sparse attention: a small second attention (the INDEXER) scores
every earlier key for each query, the ``topk`` highest are kept, and the
main heads attend over the kept keys only — the first mask in this repo
that is DATA (``ops/attention.py``'s masks are compile-time tables).

One sequence, ``T`` positions, after the caller's projections, norms and
rotary embedding:

- index scores ``I[t, s] = Hi^-½ · Di^-½ · Σ_j w[t, j] · relu(qI[t, j] ·
  kI[s])`` for ``s <= t`` (``Hi`` indexer heads of ``Di``, ONE key head),
  float32 at ``Precision.HIGHEST`` as ``ops/moe.route`` is;
- ``S_t`` = the ``topk`` keys ``s <= t`` with the largest ``I[t, s]``
  (every key while ``t < topk``), ties to the smaller ``s``, EXACT: the
  ``topk``-th largest score of a row is found by a radix search over the
  scores' bit patterns, not by a sort, in ONE Pallas kernel
  (``threshold``) that takes a query block's keys where XLA holds them,
  in VMEM: a tile of ``ROW_TILE`` rows of the chunks the block can see
  (and of no other) is laid down once, flipped to signed order, and
  every counting pass reads it there, ``RADIX_BITS`` = 1 bit a pass —
  each of the 32 passes counts the keys at or above ONE candidate, 32
  compare-and-counts a key, where four bits a pass cost 8 x 15: the
  right trade only while every pass was a pass of its own over the
  block's keys — then one more count, of the keys above the threshold;
  keys above it are kept, keys equal to it in order of ``s`` until
  ``topk`` are;
- ``o[t, h] = Σ_{s in S_t} softmax_{s in S_t}(q[t, h] · k[s, g(h)] ·
  D^-½) v[s, g(h)]``: three Pallas flash kernels of this module (forward,
  dq, dk + dv) that read the selection as BITS — masked dense: every
  causal block is multiplied and the selection applied inside the kernel,
  blocks above the diagonal are skipped, the query heads that share a
  key/value head go through one grid step (its keys, values and mask
  loaded once);
- the indexer's loss ``L_I = mean_t KL(p_t ‖ softmax_{s in S_t} I[t,
  s])``, ``p_t`` = the main heads' probabilities over ``S_t`` summed over
  the heads and normalised, under ``stop_gradient`` (a fourth kernel adds
  them up a block of queries at a time from the forward's log-sum-exp),
  computed WITH its gradients in one pass over the scores: the row's
  normaliser ``logsumexp_{s in S_t} I[t, s]`` comes out of the selection
  pass (``select(with_loss=True)`` reads it off the search's keys as it
  keeps them), so the loss multiplies a chunk of keys once and takes the
  KL's terms, their gradient and its pull-back from that one product. No
  gradient flows through ``S_t``.

Nothing ``[T, T]`` exists but the selection's bits, and the causal half
is not multiplied in full: scores, selection and loss each run ONE loop
over the query blocks, and inside it over the chunks of ``KEY_CHUNK_BLOCKS``
blocks of keys the block can see — a loop whose length is data
(``causal_scores``, ``threshold``'s kernel, ``keep_chunk``, ``index_loss``),
so the program holds one body whatever the window's length. The selection is
``[T / 32, T]`` int32 (``pack`` / ``unpack``: bit ``b`` of row ``r`` of
query block ``i`` is query ``i · block + b · block / 32 + r``, so a kernel
unpacks a block with 32 aligned row slabs): the caller keeps THAT across its
rematerialised backward (``SELECTION_NAME``), and the loss with its
gradients (``LOSS_NAME``), so the search and the loss run once a forward
pass.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.ad_checkpoint import checkpoint_name
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

SELECTION_NAME = "dsa_selection"    # the residuals a remat policy keeps:
LOSS_NAME = "dsa_index_loss"        # the bits; the loss and its gradients
KEY_CHUNK_BLOCKS = 3    # query blocks' worth of keys scored at a time
# bits of the threshold fixed by one counting pass: b bits a pass cost
# (32 / b)(2^b - 1) compare-and-counts a key — 32 at 1, 48 at 2, 120 at 4
# — and with the rows resident in VMEM a pass costs nothing else (on the
# chip, PR 45: the kernel 3.5 | 5.3 | 13.8 ms a sequence-layer of Keye's)
RADIX_BITS = 1
# rows searched at a time: their counts, their one candidate and a column
# of their keys are 3 x 16 of the 64 vector registers
ROW_TILE = 128
WORD = 32               # queries a word of the selection holds
LANES = 128
MASKED = -1e30
VMEM_LIMIT = 100 * 2 ** 20
# the search's own: what a kernel may take, XLA cannot hold its operand in
SEARCH_VMEM_LIMIT = 32 * 2 ** 20
NT = (((1,), (1,)), ((), ()))       # a · bᵀ


# ---- the indexer: scores and the exact top-k ----------------------------

def index_scores(q_i: jax.Array, w_i: jax.Array, k_i: jax.Array):
    """``q_i`` [Bq, Hi, Di], ``w_i`` [Bq, Hi], ``k_i`` [Tk, Di] float32 →
    ``I`` [Bq, Tk] float32 (no mask)."""
    hi, di = q_i.shape[1], q_i.shape[2]
    with jax.named_scope("ddq.indexer_scores"):
        s = jnp.einsum("qhd,kd->qhk", q_i, k_i,
                       precision=lax.Precision.HIGHEST,
                       preferred_element_type=jnp.float32)
        return (hi ** -0.5 * di ** -0.5) * jnp.sum(
            w_i[:, :, None] * jax.nn.relu(s), axis=1)


def _ordered_bits(x: jax.Array) -> jax.Array:
    """float32 → uint32 whose unsigned order is the floats' order (-0 is
    +0; every finite float maps above 0)."""
    b = lax.bitcast_convert_type(jnp.where(x == 0, 0.0, x), jnp.int32)
    b = b ^ ((b >> 31) & jnp.int32(0x7FFFFFFF))
    return lax.bitcast_convert_type(b, jnp.uint32) ^ jnp.uint32(0x80000000)


def _scores_of(u: jax.Array) -> jax.Array:
    """``_ordered_bits``' inverse: the score a key was made from (-0, and
    whatever else compares equal to 0, reads +0)."""
    b = lax.bitcast_convert_type(u ^ jnp.uint32(0x80000000), jnp.int32)
    return lax.bitcast_convert_type(
        b ^ ((b >> 31) & jnp.int32(0x7FFFFFFF)), jnp.float32)


def ordered_keys(scores: jax.Array, valid: jax.Array) -> jax.Array:
    """What the search compares: ``_ordered_bits`` of the valid entries,
    0 (below every score) elsewhere."""
    return jnp.where(valid, _ordered_bits(scores), jnp.uint32(0))


def _threshold_kernel(chunks_ref, u_ref, tau_ref, room_ref, keys_ref, *,
                      topk: int):
    """The search of one tile of rows: the tile's columns, the first
    ``chunks`` chunks of them, come out of ``u_ref`` [R, T] into
    ``keys_ref`` [T / chunk, tile, chunk] once; every counting pass reads
    them there, the counts lane-wise in registers."""
    _, tile, chunk = keys_ref.shape
    rows = pl.ds(pl.multiple_of(pl.program_id(0) * tile, tile), tile)
    lanes = LANES if chunk % LANES == 0 else chunk
    chunks = chunks_ref[0]
    top = jnp.int32(-2 ** 31)

    def bring(c, _):
        # Mosaic compares signed words: with the top bit flipped, once,
        # the signed order of what lies here is the keys' unsigned order
        keys_ref[c] = u_ref[rows, pl.ds(pl.multiple_of(c * chunk, chunk),
                                        chunk)] ^ jnp.uint32(2 ** 31)

    lax.fori_loop(0, chunks, bring, None)

    def count(at_or_above):
        """Keys of each row at or above each candidate [tile, 1] (bit
        patterns of unsigned words) → as many [tile, 1] int32."""
        cands = [jnp.broadcast_to(c ^ top, (tile, lanes))
                 for c in at_or_above]

        def one_chunk(c, counts):
            for j in range(chunk // lanes):
                x = lax.bitcast_convert_type(
                    keys_ref[c, :, pl.ds(j * lanes, lanes)], jnp.int32)
                counts = [n + (x >= cand).astype(jnp.int32)
                          for n, cand in zip(counts, cands)]
            return counts

        counts = lax.fori_loop(0, chunks, one_chunk, [
            jnp.zeros((tile, lanes), jnp.int32) for _ in cands])
        return [jnp.sum(n, axis=1, keepdims=True) for n in counts]

    def one_pass(i, tau):
        # the largest prefix with at least ``topk`` keys at or above it
        shift = 32 - RADIX_BITS * (i + 1)
        enough = [(n >= topk).astype(jnp.int32) for n in count(
            [tau | (jnp.int32(d) << shift)
             for d in range(1, 2 ** RADIX_BITS)])]
        return tau | (sum(enough) << shift)

    tau = lax.fori_loop(0, 32 // RADIX_BITS, one_pass,
                        jnp.zeros((tile, 1), jnp.int32))
    tau_ref[...] = tau
    # tau + 1 cannot wrap: no float's key is all ones
    room_ref[...] = topk - count([tau + 1])[0]


def threshold(u: jax.Array, topk: int, chunk: int, chunks, interpret: bool):
    """Rows of ``ordered_keys`` ``u`` [R, T], of which the first
    ``chunks`` (traced) chunks of ``chunk`` columns are read → (``tau``
    [R] uint32, the ``topk``-th largest key of each row — 0 where a row
    has fewer —, ``room`` [R] int32, how many keys EQUAL to it are kept
    after every larger one). Exact. One kernel, a step a tile of
    ``ROW_TILE`` rows (every row where they do not divide ``R``); ``u``
    is a VMEM operand, so a caller whose ``u`` XLA already holds there (a
    query block of ``select``) hands it over without a copy — and ``u``
    with a tile's columns beside it has to FIT there: 4 R T bytes + a
    quarter of that at the Keye widths (35 + 9 MB of 128); a window four
    times as long wants a smaller query block."""
    rows, t = u.shape
    tile = ROW_TILE if rows % ROW_TILE == 0 else rows
    out = pl.BlockSpec((tile, 1), lambda i, chunks: (i, 0))
    tau, room = pl.pallas_call(
        functools.partial(_threshold_kernel, topk=topk),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1, grid=(rows // tile,),
            in_specs=[pl.BlockSpec(memory_space=pltpu.VMEM)],
            out_specs=[out, out],
            scratch_shapes=[pltpu.VMEM((t // chunk, tile, chunk), u.dtype)]),
        out_shape=[jax.ShapeDtypeStruct((rows, 1), jnp.int32)] * 2,
        compiler_params=_params("parallel", limit=SEARCH_VMEM_LIMIT),
        interpret=interpret, name="topk_threshold")(
            jnp.reshape(chunks, (1,)).astype(jnp.int32), u)
    return lax.bitcast_convert_type(tau[:, 0], jnp.uint32), room[:, 0]


def keep_chunk(uc: jax.Array, tau: jax.Array, room: jax.Array,
               seen: jax.Array):
    """One chunk's columns ``uc`` [R, C] of the keys → (kept bool [R, C]:
    above ``tau``, or equal to it and among the row's first ``room`` such
    in order of column — ``seen`` [R] int32 of them lie in earlier chunks
    —, never an invalid entry; ``seen`` after this chunk)."""
    equal = uc == tau[:, None]
    rank = seen[:, None] + jnp.cumsum(equal, axis=-1, dtype=jnp.int32)
    keep = (uc > tau[:, None]) | (equal & (rank <= room[:, None]))
    return keep & (uc > 0), seen + jnp.sum(equal, axis=-1, dtype=jnp.int32)


def kept_logsumexp(norm, uc: jax.Array, kept: jax.Array):
    """The running log-sum-exp of each row's KEPT scores as (maximum,
    sum of ``exp(score - maximum)``), both [R] float32, after one more
    chunk: ``uc`` [R, C] the chunk's keys, ``kept`` bool [R, C]."""
    m, l = norm
    s = jnp.where(kept, _scores_of(uc), MASKED)
    m_next = jnp.maximum(m, jnp.max(s, axis=-1))
    # a row with nothing kept so far has m_next = MASKED: exp(s - m_next)
    # reads 1 on what it dropped, so the sum takes the kept entries only
    return m_next, l * jnp.exp(m - m_next) + jnp.sum(
        jnp.where(kept, jnp.exp(s - m_next[:, None]), 0.0), axis=-1)


def topk_mask(scores: jax.Array, valid: jax.Array, topk: int,
              interpret: bool = True) -> jax.Array:
    """Rows of ``scores`` [R, T] float32, ``valid`` [R, T] bool → bool
    [R, T]: the ``topk`` valid entries with the largest score in each row
    (all of them where a row has no more), ties to the smaller column.
    Exact. (The whole width as one chunk: ``select`` runs the same two
    functions over the chunks a query block can see.)"""
    u = ordered_keys(scores, valid)
    tau, room = threshold(u, topk, u.shape[1], 1, interpret)
    return keep_chunk(u, tau, room, jnp.zeros(u.shape[0], jnp.int32))[0]


def pack(keep: jax.Array) -> jax.Array:
    """One query block's selection bool [block, C] → int32 [block / 32,
    C]: bit ``b`` of row ``r`` = ``keep[b · block / 32 + r]``."""
    block, c = keep.shape
    bits = keep.reshape(WORD, block // WORD, c).astype(jnp.uint32)
    words = jnp.sum(bits << jnp.arange(WORD, dtype=jnp.uint32)[:, None, None],
                    axis=0, dtype=jnp.uint32)
    return lax.bitcast_convert_type(words, jnp.int32)


def unpack(words: jax.Array) -> jax.Array:
    """``pack``'s inverse: int32 [R, C] → bool [32 R, C]."""
    r, c = words.shape
    bits = (words[None] >> jnp.arange(WORD, dtype=jnp.int32)[:, None, None]
            ) & 1
    return bits.reshape(WORD * r, c).astype(bool)


def unpack_selection(bits, block: int) -> np.ndarray:
    """A whole sequence's selection on the host: int32 [T / 32, T] → bool
    [T, T] (query, key)."""
    bits = np.asarray(bits)
    rows = block // WORD
    blocks = bits.reshape(-1, 1, rows, bits.shape[-1])
    keep = (blocks >> np.arange(WORD, dtype=np.int32)[None, :, None, None]
            ) & 1
    return keep.reshape(-1, bits.shape[-1]).astype(bool)


def _key_chunk(t_pad: int, block: int) -> int:
    """Keys scored at a time: the most whole blocks up to
    ``KEY_CHUNK_BLOCKS`` that divide the padded window."""
    nb = t_pad // block
    return block * max(m for m in range(1, KEY_CHUNK_BLOCKS + 1)
                       if nb % m == 0)


def _chunks(i, block: int, chunk: int):
    """Key chunks that hold a key query block ``i`` (traced) can see."""
    return ((i + 1) * block + chunk - 1) // chunk


def _at(x: jax.Array, c, chunk: int, axis: int = 0) -> jax.Array:
    return lax.dynamic_slice_in_dim(x, c * chunk, chunk, axis=axis)


def _put(buf: jax.Array, x: jax.Array, c, chunk: int,
         axis: int = 0) -> jax.Array:
    return lax.dynamic_update_slice_in_dim(buf, x, c * chunk, axis=axis)


def causal_scores(qb: jax.Array, wb: jax.Array, k_i: jax.Array, chunks,
                  chunk: int, keys=None) -> jax.Array:
    """Index scores of a block of queries against the first ``chunks``
    (traced) chunks of ``chunk`` keys, a chunk at a time: ``qb`` [block,
    Hi, Di], ``wb`` [block, Hi], ``k_i`` [T, Di] → [block, T] float32, 0
    from there on (the causal half is not multiplied in full: the loop's
    length is data, its body one program). With ``keys(scores, c)`` the
    search's uint32 keys of each chunk's scores stand in their place."""
    def one_chunk(c, buf):
        scores = index_scores(qb, wb, _at(k_i, c, chunk))
        return _put(buf, keys(scores, c) if keys else scores, c, chunk, 1)

    return lax.fori_loop(0, chunks, one_chunk, jnp.zeros(
        (qb.shape[0], k_i.shape[0]), jnp.uint32 if keys else jnp.float32))


def select(q_i: jax.Array, w_i: jax.Array, k_i: jax.Array, *, topk: int,
           block: int, t_real: int, with_loss: bool = False,
           interpret: bool = True):
    """The selection of one sequence padded to ``T`` (a multiple of
    ``block``): ``q_i`` [T, Hi, Di], ``w_i`` [T, Hi], ``k_i`` [T, Di]
    float32 → (bits int32 [T/32, T], pairs selected by the first
    ``t_real`` queries, int32, and with ``with_loss`` what the indexer's
    loss divides by: the log-sum-exp of each query's index scores over
    the keys it KEPT, [T] float32 — ``None`` without). ONE loop over the
    query blocks; inside it the scores, the search's counting passes and
    the keep each run over the key chunks the block can see, and over no
    other; the normaliser is read off the search's keys as the keep walks
    them, so the loss never scores the block for it."""
    t_pad, hi, di = q_i.shape
    nb, chunk = t_pad // block, _key_chunk(t_pad, block)

    def one_block(xs):
        qb, wb, i = xs
        chunks = _chunks(i, block, chunk)
        t_pos = i * block + jnp.arange(block)[:, None]

        def keys(scores, c):
            return ordered_keys(
                scores, c * chunk + jnp.arange(chunk)[None, :] <= t_pos)

        u = causal_scores(qb, wb, k_i, chunks, chunk, keys)
        with jax.named_scope("ddq.topk"):
            tau, room = threshold(u, topk, chunk, chunks, interpret)

            def keep(c, carry):
                bits, seen, kept, norm = carry
                uc = _at(u, c, chunk, 1)
                kc, seen = keep_chunk(uc, tau, room, seen)
                if with_loss:
                    norm = kept_logsumexp(norm, uc, kc)
                return (_put(bits, pack(kc), c, chunk, 1), seen,
                        kept + jnp.sum(kc & (t_pos < t_real),
                                       dtype=jnp.int32), norm)

            bits, _, kept, norm = lax.fori_loop(0, chunks, keep, (
                jnp.zeros((block // WORD, t_pad), jnp.int32),
                jnp.zeros(block, jnp.int32), jnp.zeros((), jnp.int32),
                (jnp.full(block, MASKED, jnp.float32),
                 jnp.zeros(block, jnp.float32)) if with_loss else None))
            # every query keeps itself at least: the sum is positive
            return bits, kept, (norm[0] + jnp.log(norm[1])
                                if with_loss else None)

    bits, kept, lse_i = lax.map(one_block, (
        q_i.reshape(nb, block, hi, di), w_i.reshape(nb, block, hi),
        jnp.arange(nb)))
    return (bits.reshape(t_pad // WORD, t_pad), jnp.sum(kept),
            lse_i.reshape(t_pad) if with_loss else None)


# ---- the core: attention over the selected keys (Pallas) ----------------

def _lanes(x: jax.Array, n: int) -> jax.Array:
    """A lane-replicated ``[rows, 128]`` statistic as ``[rows, n]``."""
    return x[:, :n] if n < LANES else pltpu.repeat(x, n // LANES, axis=1)


def _set_bias(bits_ref, bias_ref):
    """The block's selection as an additive mask in ``bias_ref`` [bq,
    bkv] float32: 0 where kept, ``MASKED`` elsewhere."""
    words = bits_ref[...]
    rows = words.shape[0]
    for b in range(WORD):
        bias_ref[pl.ds(b * rows, rows), :] = jnp.where(
            ((words >> b) & 1) == 1, 0.0, MASKED)


def _fwd_kernel(q_ref, k_ref, v_ref, bits_ref, o_ref, lse_ref,
                m_ref, l_ref, acc_ref, bias_ref):
    i, j = pl.program_id(1), pl.program_id(2)
    group, _, d = q_ref.shape
    bkv = k_ref.shape[0]

    @pl.when(j == 0)
    def _():
        m_ref[...] = jnp.full_like(m_ref, MASKED)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    @pl.when(j <= i)        # blocks above the diagonal hold no key
    def _():
        _set_bias(bits_ref, bias_ref)
        bias, k, v = bias_ref[...], k_ref[...], v_ref[...]
        for g in range(group):
            s = lax.dot_general(q_ref[g], k, NT,
                                preferred_element_type=jnp.float32) + bias
            m_prev = m_ref[g]
            m_next = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
            alpha = jnp.exp(m_prev - m_next)
            # a row all masked so far reads p = 1 here; the first real
            # key's alpha = exp(MASKED - m) = 0 wipes it
            p = jnp.exp(s - _lanes(m_next, bkv))
            l_ref[g] = alpha * l_ref[g] + jnp.sum(p, axis=1, keepdims=True)
            m_ref[g] = m_next
            acc_ref[g] = acc_ref[g] * _lanes(alpha, d) + lax.dot(
                p.astype(v.dtype), v, preferred_element_type=jnp.float32)

    @pl.when(j == pl.num_programs(2) - 1)
    def _():
        for g in range(group):
            l = l_ref[g]
            o_ref[:, g * d:(g + 1) * d] = (
                acc_ref[g] / _lanes(l, d)).astype(o_ref.dtype)
            # a query's statistic, lane-replicated, laid along the lanes
            lse_ref[pl.ds(g, 1), :] = (m_ref[g] + jnp.log(l)).T[:1, :]


def _dq_kernel(q_ref, k_ref, v_ref, bits_ref, do_ref, lse_ref, di_ref,
               dq_ref, acc_ref, bias_ref):
    i, j = pl.program_id(1), pl.program_id(2)
    group, _, d = q_ref.shape

    @pl.when(j == 0)
    def _():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    @pl.when(j <= i)
    def _():
        _set_bias(bits_ref, bias_ref)
        bias, k, v = bias_ref[...], k_ref[...], v_ref[...]
        for g in range(group):
            s = lax.dot_general(q_ref[g], k, NT,
                                preferred_element_type=jnp.float32) + bias
            p = jnp.exp(s - jnp.expand_dims(lse_ref[g], -1))
            dp = lax.dot_general(do_ref[:, g * d:(g + 1) * d], v, NT,
                                 preferred_element_type=jnp.float32)
            ds = (dp - jnp.expand_dims(di_ref[g], -1)) * p
            acc_ref[g] += lax.dot(ds.astype(k.dtype), k,
                                  preferred_element_type=jnp.float32)

    @pl.when(j == pl.num_programs(2) - 1)
    def _():
        dq_ref[...] = acc_ref[...].astype(dq_ref.dtype)


def _dkv_kernel(q_ref, k_ref, v_ref, bits_ref, do_ref, lse_ref, di_ref,
                dk_ref, dv_ref, dk_acc, dv_acc, bias_ref):
    j, i = pl.program_id(1), pl.program_id(2)
    group, _, d = q_ref.shape

    @pl.when(i == 0)
    def _():
        dk_acc[...] = jnp.zeros_like(dk_acc)
        dv_acc[...] = jnp.zeros_like(dv_acc)

    @pl.when(i >= j)
    def _():
        # scores transposed, [bkv, bq]: a query's statistics lie along
        # the lanes as they are stored, and both products into dk and dv
        # contract over the queries without a transpose a head
        _set_bias(bits_ref, bias_ref)
        bias, k, v = bias_ref[...].T, k_ref[...], v_ref[...]
        for g in range(group):
            q, do = q_ref[g], do_ref[:, g * d:(g + 1) * d]
            s = lax.dot_general(k, q, NT,
                                preferred_element_type=jnp.float32) + bias
            p = jnp.exp(s - lse_ref[pl.ds(g, 1), :])
            dv_acc[...] += lax.dot(p.astype(do.dtype), do,
                                   preferred_element_type=jnp.float32)
            dp = lax.dot_general(v, do, NT,
                                 preferred_element_type=jnp.float32)
            ds = (dp - di_ref[pl.ds(g, 1), :]) * p
            dk_acc[...] += lax.dot(ds.astype(q.dtype), q,
                                   preferred_element_type=jnp.float32)

    @pl.when(i == pl.num_programs(2) - 1)
    def _():
        dk_ref[...] = dk_acc[...].astype(dk_ref.dtype)
        dv_ref[...] = dv_acc[...].astype(dv_ref.dtype)


def _probs_kernel(i_ref, q_ref, k_ref, bits_ref, lse_ref, out_ref,
                  bias_ref):
    j, n = pl.program_id(0), pl.program_id(1)
    group = q_ref.shape[0]

    @pl.when(n == 0)
    def _():
        out_ref[...] = jnp.zeros_like(out_ref)
        _set_bias(bits_ref, bias_ref)

    @pl.when(j <= i_ref[0])
    def _():
        bias, k = bias_ref[...], k_ref[...]
        for g in range(group):
            s = lax.dot_general(q_ref[g], k, NT,
                                preferred_element_type=jnp.float32) + bias
            out_ref[...] += jnp.exp(s - jnp.expand_dims(lse_ref[g], -1))


def _params(*semantics: str, limit: int = VMEM_LIMIT):
    return pltpu.CompilerParams(dimension_semantics=semantics,
                                vmem_limit_bytes=limit)


def _specs(hkv: int, group: int, block: int, d: int, q_major: bool):
    """Block specs on a grid ``(sequence x kv head, i, j)`` (``q_major``)
    or ``(sequence x kv head, j, i)``: of q (head-major), of k and v, of
    the selection's bits, of a per-query statistic and of o and its
    cotangent (token-major, a kv head's query heads side by side); a step
    above the diagonal names the diagonal's blocks again, so nothing is
    fetched for it."""
    if q_major:
        qi = lambda a, b: a                         # noqa: E731
        kj = lambda a, b: jnp.minimum(a, b)         # noqa: E731
    else:
        qi = lambda a, b: jnp.maximum(a, b)         # noqa: E731
        kj = lambda a, b: a                         # noqa: E731
    return dict(
        q=pl.BlockSpec((None, group, block, d),
                       lambda n, a, b: (n, 0, qi(a, b), 0)),
        k=pl.BlockSpec((None, block, d), lambda n, a, b: (n, kj(a, b), 0)),
        bits=pl.BlockSpec((None, block // WORD, block),
                          lambda n, a, b: (n // hkv, qi(a, b), kj(a, b))),
        row=pl.BlockSpec((None, group, block),
                         lambda n, a, b: (n, 0, qi(a, b))),
        o=pl.BlockSpec((None, block, group * d),
                       lambda n, a, b: (n // hkv, qi(a, b), n % hkv)))


def _forward(q, k, v, bits, block: int, interpret: bool):
    n, group, t, d = q.shape
    b = bits.shape[0]
    nb = t // block
    sp = _specs(n // b, group, block, d, True)
    stat = pltpu.VMEM((group, block, LANES), jnp.float32)
    return pl.pallas_call(
        _fwd_kernel, grid=(n, nb, nb),
        in_specs=[sp["q"], sp["k"], sp["k"], sp["bits"]],
        out_specs=[sp["o"], sp["row"]],
        out_shape=[jax.ShapeDtypeStruct((b, t, n // b * group * d), q.dtype),
                   jax.ShapeDtypeStruct((n, group, t), jnp.float32)],
        scratch_shapes=[stat, stat,
                        pltpu.VMEM((group, block, d), jnp.float32),
                        pltpu.VMEM((block, block), jnp.float32)],
        compiler_params=_params("parallel", "parallel", "arbitrary"),
        interpret=interpret, name="sparse_core_fwd")(q, k, v, bits)


@functools.partial(jax.custom_vjp, nondiff_argnums=(4, 5))
def sparse_core(q: jax.Array, k: jax.Array, v: jax.Array, bits: jax.Array,
                block: int, interpret: bool):
    """Attention over the selected keys: ``q`` [B·Hkv, G, T, D] (already
    scaled), ``k`` / ``v`` [B·Hkv, T, D] in the compute dtype, ``bits``
    [B, T/32, T] from ``select``; ``T`` a multiple of ``block`` → (``o``
    [B, T, H·D], the log-sum-exp of each query's kept scores [B·Hkv, G, T]
    float32, which carries no gradient). Every selected pair and no other
    enters the softmax."""
    return _forward(q, k, v, bits, block, interpret)


def _core_fwd(q, k, v, bits, block, interpret):
    out, lse = _forward(q, k, v, bits, block, interpret)
    return (out, lse), (q, k, v, bits, out, lse)


def _core_bwd(block, interpret, res, cts):
    q, k, v, bits, out, lse = res
    do = cts[0]
    n, group, t, d = q.shape
    b = bits.shape[0]
    nb = t // block
    heads = lambda x: x.reshape(b, t, n // b, group, d)  # noqa: E731
    di = jnp.einsum("btngd,btngd->bngt", heads(out), heads(do),
                    preferred_element_type=jnp.float32).reshape(n, group, t)
    sp = _specs(n // b, group, block, d, True)
    bias = pltpu.VMEM((block, block), jnp.float32)
    dq = pl.pallas_call(
        _dq_kernel, grid=(n, nb, nb),
        in_specs=[sp["q"], sp["k"], sp["k"], sp["bits"], sp["o"],
                  sp["row"], sp["row"]],
        out_specs=sp["q"], out_shape=jax.ShapeDtypeStruct(q.shape, q.dtype),
        scratch_shapes=[pltpu.VMEM((group, block, d), jnp.float32), bias],
        compiler_params=_params("parallel", "parallel", "arbitrary"),
        interpret=interpret, name="sparse_core_dq")(
            q, k, v, bits, do, lse, di)
    sp = _specs(n // b, group, block, d, False)
    acc = pltpu.VMEM((block, d), jnp.float32)
    dk, dv = pl.pallas_call(
        _dkv_kernel, grid=(n, nb, nb),
        in_specs=[sp["q"], sp["k"], sp["k"], sp["bits"], sp["o"],
                  sp["row"], sp["row"]],
        out_specs=[sp["k"], sp["k"]],
        out_shape=[jax.ShapeDtypeStruct(k.shape, k.dtype),
                   jax.ShapeDtypeStruct(v.shape, v.dtype)],
        scratch_shapes=[acc, acc, bias],
        compiler_params=_params("parallel", "parallel", "arbitrary"),
        interpret=interpret, name="sparse_core_dkv")(
            q, k, v, bits, do, lse, di)
    return dq, dk, dv, np.zeros(bits.shape, jax.dtypes.float0)


sparse_core.defvjp(_core_fwd, _core_bwd)


def head_probabilities(i, q_blk, k, bits_blk, lse_blk, block: int,
                       interpret: bool) -> jax.Array:
    """``Σ_h softmax_{s in S_t}(q[t, h] · k[s])`` of query block ``i``
    (traced) of one sequence against the keys ``k`` [Hkv, Tk, D]:
    ``q_blk`` [Hkv, G, block, D], ``bits_blk`` [block/32, Tk], ``lse_blk``
    [Hkv, G, block] from ``sparse_core`` → [block, Tk] float32, 0 outside
    the selection."""
    hkv, group, _, d = q_blk.shape
    tk = k.shape[1]
    grid = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1, grid=(tk // block, hkv),
        in_specs=[
            pl.BlockSpec((None, group, block, d),
                         lambda j, n, i: (n, 0, 0, 0)),
            pl.BlockSpec((None, block, d),
                         lambda j, n, i: (n, jnp.minimum(j, i[0]), 0)),
            pl.BlockSpec((block // WORD, block),
                         lambda j, n, i: (0, jnp.minimum(j, i[0]))),
            pl.BlockSpec((None, group, block), lambda j, n, i: (n, 0, 0))],
        out_specs=pl.BlockSpec((block, block), lambda j, n, i: (0, j)),
        scratch_shapes=[pltpu.VMEM((block, block), jnp.float32)])
    return pl.pallas_call(
        _probs_kernel, grid_spec=grid,
        out_shape=jax.ShapeDtypeStruct((block, tk), jnp.float32),
        compiler_params=_params("parallel", "arbitrary"),
        interpret=interpret, name="sparse_head_probs")(
            jnp.reshape(i, (1,)).astype(jnp.int32), q_blk, k, bits_blk,
            lse_blk)


# ---- the indexer's loss --------------------------------------------------

def index_loss(q_i: jax.Array, w_i: jax.Array, k_i: jax.Array, q: jax.Array,
               k: jax.Array, lse: jax.Array, bits: jax.Array,
               lse_i: jax.Array, *, block: int, t_real: int,
               interpret: bool):
    """``Σ_{t < t_real} KL(p_t ‖ softmax_{S_t} I[t, ·])`` of one sequence
    AND its gradients by ``q_i``, ``w_i`` and ``k_i`` (the caller divides
    by the count), in ONE loop over the query blocks and inside it ONE
    over the key chunks the block can see: with the main heads'
    probabilities ``p`` over the kept keys and ``select``'s normaliser
    ``lse_i`` [T] in hand, a chunk's score product (the one its pull-back
    needs anyway) gives ``log softmax = score - lse_i``, the chunk's KL
    terms, the gradient by its scores ``exp(log softmax) · Σ_S p - p`` on
    the kept pairs, and that gradient taken back through the product —
    each pair is scored once, forward and backward before the next chunk,
    nothing [T, T] waits for a backward pass. ``q`` [Hkv, G, T, D] scaled,
    ``k`` [Hkv, T, D], ``lse`` [Hkv, G, T]; nothing differentiates
    through this."""
    hkv, group, t_pad, d = q.shape
    hi, di = q_i.shape[1:]
    nb, chunk = t_pad // block, _key_chunk(t_pad, block)

    def one_block(g_k, xs):
        qb_i, wb_i, qb, lse_b, words, norm, i = xs
        real = i * block + jnp.arange(block) < t_real
        p = head_probabilities(i, qb, k, words, lse_b, block,
                               interpret) / (hkv * group)
        p_sum = jnp.sum(p, axis=-1, keepdims=True)  # 0 outside the kept

        def one_chunk(c, carry):
            kl, g_q, g_w, g_k = carry
            scores, pull = jax.vjp(index_scores, qb_i, wb_i,
                                   _at(k_i, c, chunk))
            keep, pc = unpack(_at(words, c, chunk, 1)), _at(p, c, chunk, 1)
            log_q = scores - norm[:, None]
            kl = kl + jnp.sum(jnp.where(
                keep, jax.scipy.special.xlogy(pc, pc) - pc * log_q, 0.0), -1)
            dq, dw, dk = pull(jnp.where(
                keep & real[:, None], jnp.exp(log_q) * p_sum - pc, 0.0))
            return kl, g_q + dq, g_w + dw, _put(
                g_k, _at(g_k, c, chunk) + dk, c, chunk)

        kl, g_q, g_w, g_k = lax.fori_loop(
            0, _chunks(i, block, chunk), one_chunk,
            (jnp.zeros(block, jnp.float32), jnp.zeros_like(qb_i),
             jnp.zeros_like(wb_i), g_k))
        return g_k, (jnp.sum(jnp.where(real, kl, 0.0)), g_q, g_w)

    g_k, (kl, g_q, g_w) = lax.scan(one_block, jnp.zeros_like(k_i), (
        q_i.reshape(nb, block, hi, di), w_i.reshape(nb, block, hi),
        jnp.moveaxis(q.reshape(hkv, group, nb, block, d), 2, 0),
        jnp.moveaxis(lse.reshape(hkv, group, nb, block), 2, 0),
        bits.reshape(nb, block // WORD, t_pad), lse_i.reshape(nb, block),
        jnp.arange(nb)))
    return jnp.sum(kl), (g_q.reshape(q_i.shape), g_w.reshape(w_i.shape), g_k)


def sparse_attention(q: jax.Array, k: jax.Array, v: jax.Array,
                     q_i: jax.Array, w_i: jax.Array, k_i: jax.Array, *,
                     topk: int, block: int, t_real: int, with_loss: bool,
                     interpret: bool = False):
    """The mixer's core over a batch whose ``T`` is a multiple of
    ``block`` (itself a multiple of 32; on the chip of 256) with the first
    ``t_real`` positions real (padded keys lie in every real query's
    future): ``q`` [B, H, T, D], ``k`` / ``v`` [B, Hkv, T, D] in the
    compute dtype, ``q_i`` [B, T, Hi, Di], ``w_i`` [B, T, Hi], ``k_i`` [B,
    T, Di] float32 (the indexer's, which read a ``stop_gradient`` input) →
    (``o`` [B, T, H·D], counters: ``selected`` / ``causal`` pairs of the
    real queries over the batch as float32, ``index_loss`` = the batch's
    mean over real queries of the KL (0 without ``with_loss``), and
    ``bits`` [B, T/32, T])."""
    b, h, t, d = q.shape
    hkv = k.shape[1]
    q = (q * jnp.asarray(d ** -0.5, q.dtype)).reshape(
        b * hkv, h // hkv, t, d)
    k, v = k.reshape(b * hkv, t, d), v.reshape(b * hkv, t, d)

    def selection(xs):
        bits, kept, lse_i = select(*xs, topk=topk, block=block,
                                   t_real=t_real, with_loss=with_loss,
                                   interpret=interpret)
        return bits, kept.astype(jnp.float32), lse_i

    bits, kept, lse_i = lax.map(selection, (q_i, w_i, k_i))
    bits = checkpoint_name(lax.stop_gradient(bits), SELECTION_NAME)
    with jax.named_scope("ddq.sparse_core"):
        out, lse = sparse_core(q, k, v, bits, block, interpret)
    loss = jnp.zeros((), jnp.float32)
    if with_loss:
        per_seq = lambda x: x.reshape((b, hkv) + x.shape[1:])  # noqa: E731
        indexer = (q_i, w_i, k_i)
        with jax.named_scope("ddq.indexer_loss"):
            # value and gradients in ONE pass over the scores, both kept
            # across the caller's rematerialisation (``LOSS_NAME``); the
            # loss the caller differentiates is that value plus the
            # gradients' first-order term, which is zero
            total, grads = lax.map(lambda xs: index_loss(
                *xs, block=block, t_real=t_real, interpret=interpret),
                lax.stop_gradient((*indexer, per_seq(q), per_seq(k),
                                   per_seq(lse), bits, lse_i)))
            value, grads = checkpoint_name(jax.tree.map(
                lambda x: x / (b * t_real), (jnp.sum(total), grads)),
                LOSS_NAME)
            loss = value + sum(
                jnp.vdot(g, x - lax.stop_gradient(x))
                for g, x in zip(grads, indexer))
        # the loss before the output moves on: its value is read at the
        # step's end only, and a scheduler free to compute it there keeps
        # q, k and the indexer's inputs of every layer alive until then
        out, loss = lax.optimization_barrier((out, loss))
    counters = {"selected": jnp.sum(kept),
                "causal": jnp.asarray(b * (t_real * (t_real + 1) / 2.0),
                                      jnp.float32),
                "index_loss": loss, "bits": bits}
    return out, counters
