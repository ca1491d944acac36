"""Exact top-k of every row of a score matrix AS A MASK, without a sort.

Learned sparse attention (a DeepSeek-Sparse-Attention indexer:
``models/keye_vl2.py``) keeps, for each query, the k keys its index scores
rank highest, and the flash kernels read that choice as a [queries, keys]
mask (``ops/flash_attention.py``, "selected tiles"). ``lax.top_k`` at k in
the thousands lowers to a full row sort on the TPU and hands back INDICES,
which a scatter would have to turn into the mask. This finds the k-th
largest score of a row instead — a bisection over the 32 bits of the float,
one compare-and-count pass over the row a bit — and the mask is a compare
with it:

    key(x)   the float's bits as an unsigned integer in the floats' order
             (the total order ``lax.top_k`` sorts by: -0.0 below +0.0; keys
             not ``valid`` take 0, below every number)
    tau      the largest u with  #{s: key(s) >= u} >= k        (32 passes)
    selected key(s) > tau, and of the keys EQUAL to tau the first
             k - #{key > tau} by position                       (ties to the
             lower index, as ``lax.top_k`` resolves them: a second
             bisection, over the position, run only where a row has more
             equal keys than it may take)

so the result is ``lax.top_k``'s set exactly, ties included
(tests/test_index_select.py), and a row with fewer than k valid keys keeps
them all. No gradient: a selection is discrete.

Two forms, one result. ``top_k_mask`` is the rule over a score matrix
somebody else made, in XLA: the ``"dense"`` path of
``models/keye_vl2.select_keys`` (tests, tiny models, widths that are no
whole lane tiles: a ``lax.map`` over blocks of query rows whose every step
makes the block's index scores for ALL keys and bisects the whole row) and
the kernel's oracle. ``index_select`` is a causal layer's whole selection —
the index scores I[t, s] = sum_j w[t, j] relu(qI[t, j] · kI[s]) x
(D_I · J)^-0.5 AND their exact top-k over the keys s <= t — as ONE Pallas
TPU kernel (``index_select`` in a trace), what runs behind the flash
kernels. A grid step is (batch row, block of R query rows); R is also the
width of a CHUNK of keys, so block i has i + 1 chunks that hold a valid key
and everything below walks those alone — the causal triangle, not the
square:

    scores   a chunk's key strips (a lane tile of keys each), every index
             head's dot of the strip [R, 128] in the compute dtype with
             float32 accumulation, relu, x w, summed over the heads in
             float32 in the vector registers (``ops/index_loss.py``'s
             strips: two index heads a 128-lane window, ``k_index`` ITSELF
             resident in VMEM), x scale, then straight to their ordered keys
             in a [R, S] VMEM scratch — keys past the query's own position
             take the lowest. Nothing [rows, heads, S] exists.
    tau      the bisection over that scratch, a strip of rows at a time,
             the counts LANE-PARTIAL ([rows, 128] int32: lane c holds the
             hits of keys c, c + 128, ...; lanes are crossed once a pass).
             It stops before the 32nd pass once every row of the strip
             holds EXACTLY k keys at or above the threshold built so far:
             the bits still unknown cannot move a key across it (25-27
             passes a strip of 128 rows on random operands).
    ties     where the 32 passes ran out, one more counts the keys above
             and equal to tau; the bisection over the position runs under
             ``pl.when`` only where a row of the strip has more equal keys
             than it may take (the call's second output says which blocks
             did: [B, S / R] int32).
    write    the block's int8 [R, S] rows once, zeros above the diagonal.

A block whose every row has at most k valid keys (t < k) writes the causal
mask and computes nothing. The kernel keeps the keys as SIGNED integers
(the unsigned key with its top bit flipped: the same order under a signed
compare, which is the compare the vector unit has), so "0, below every
number" is INT_MIN there. Same equations and dtypes as ``index_scores`` +
``top_k_mask``: on operands whose products are exactly representable the
two masks are equal bit for bit; on bf16 operands a row's sixteen terms are
summed in another order than XLA's, so a score may differ in its last
float32 bit and a key within an ulp of a row's threshold may fall the other
way (tests/test_index_select.py holds both).
"""
from __future__ import annotations

import functools
from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from dedloc_tpu.ops.flash_attention import (
    STATE_LANES,
    _dot,
    _heads_per_block,
    _pick_block,
)
from dedloc_tpu.ops.index_loss import _at, _key_slots, _loop, _row_tile, _vmem
from dedloc_tpu.utils.backend import pallas_interpret

# query rows of a grid step — and keys of a chunk —, and query rows the
# bisection takes at a time (its threshold and its lane-partial counts, a
# [rows, 128] int32 each, stay in the vector registers beside a tile of keys)
BLOCK_ROWS, BISECT_ROWS = 256, 128
# chunks of keys a step of the passes over the keys takes (unrolled)
CHUNKS_A_STEP = 4
_LOWEST = -(2**31)  # the signed key of a position that is not valid


def _ordered_keys(scores):
    """float32 -> uint32 in the floats' TOTAL order (-0.0 below +0.0, as
    ``lax.top_k`` compares)."""
    bits = jax.lax.bitcast_convert_type(scores, jnp.uint32)
    top = jnp.uint32(1 << 31)
    return jnp.where(bits >= top, ~bits, bits | top)


def _bisect(bits: int, accept, start):
    """The largest u of ``bits`` bits, built from the top bit down, with
    ``accept(u)`` (monotone: true for every smaller u), per row."""
    def step(i, found):
        candidate = found | (jnp.uint32(1) << (jnp.uint32(bits - 1) - i))
        return jnp.where(accept(candidate), candidate, found)

    return jax.lax.fori_loop(
        jnp.uint32(0), jnp.uint32(bits), step, start
    )


def top_k_mask_and_ties(scores, valid, k: int):
    """([R, S] bool: for each row of ``scores`` [R, S] float32, the ``k``
    ``valid`` [R, S] entries with the largest score — all of them where a
    row has no more than k —, ties to the lower position; a bool: a row had
    more entries equal to its k-th largest than it may take, so the
    bisection over the position ran)."""
    rows, width = scores.shape
    keys = jnp.where(valid, _ordered_keys(scores), jnp.uint32(0))

    def count(hit):
        return jnp.sum(hit, axis=-1, dtype=jnp.int32)

    zero = jnp.zeros((rows,), jnp.uint32)
    tau = _bisect(32, lambda u: count(keys >= u[:, None]) >= k, zero)
    above = keys > tau[:, None]
    equal = valid & (keys == tau[:, None])
    need = k - count(above)  # of the equal ones: >= 1 where a row has k
    position = jax.lax.broadcasted_iota(jnp.uint32, keys.shape, 1)

    def first_needed():
        # the largest p with fewer than ``need`` equal keys BEFORE it: the
        # key at p is the last one taken
        return _bisect(
            max(width - 1, 1).bit_length(),
            lambda p: count(equal & (position < p[:, None])) < need, zero,
        )

    tied = jnp.any(count(equal) > need)
    last = jax.lax.cond(
        tied, first_needed, lambda: jnp.full((rows,), width, jnp.uint32)
    )
    return jax.lax.stop_gradient(
        above | (equal & (position <= last[:, None]))
    ), tied


def top_k_mask(scores, valid, k: int):
    """``top_k_mask_and_ties``' mask."""
    return top_k_mask_and_ties(scores, valid, k)[0]


# ------------------------------------------------- the selection as a kernel


class _Plan(NamedTuple):
    """A call's static geometry."""

    seq: int
    top_k: int
    index_heads: int  # J
    index_width: int  # D_I
    window_heads: int  # g: index heads that share a lane window
    rows: int  # R: query rows of a grid step, keys of a chunk
    lanes: int  # keys of a strip: a lane tile
    bisect_rows: int
    chunks_a_step: int  # of the passes over the keys (unrolled)

    @property
    def window(self) -> int:
        return self.window_heads * self.index_width


def _signed_keys(scores):
    """float32 -> int32 in the floats' TOTAL order under a SIGNED compare:
    ``_ordered_keys`` with the top bit flipped."""
    bits = jax.lax.bitcast_convert_type(scores, jnp.int32)
    return jnp.where(bits < 0, bits ^ jnp.int32(2**31 - 1), bits)


def _for_chunks(count, unroll: int, body, carry):
    """``carry = body(c, carry)`` for c in range(count), ``count`` traced:
    ``unroll`` chunks a loop step, then the rest one by one."""
    def step(c, carry):
        for u in range(unroll):
            carry = body(c * unroll + u, carry)
        return carry

    carry = jax.lax.fori_loop(0, count // unroll, step, carry)
    return jax.lax.fori_loop(count // unroll * unroll, count, body, carry)


def _select_kernel(qi_ref, ki_ref, w_ref, out_ref, tie_ref, wb_ref, keys_ref,
                   *, plan: _Plan):
    seq, top_k, j_heads, di, g, r, lanes, rb, unroll = plan
    n, i = pl.program_id(0), pl.program_id(1)
    first = i * r  # the block's first query; chunk i holds its diagonal
    strips = r // lanes  # key strips (lane tiles) of a chunk

    def tile_at(c, s):
        return pl.multiple_of(c * r + s * lanes, lanes)

    def causal(first_row, s, rows: int):
        """[rows, lanes] bool: the keys of the diagonal chunk's strip ``s``
        at or before the query, for ``rows`` queries from ``first_row`` of
        the block."""
        shape = (rows, lanes)
        return s * lanes + jax.lax.broadcasted_iota(jnp.int32, shape, 1) <= (
            first_row + jax.lax.broadcasted_iota(jnp.int32, shape, 0)
        )

    def zero_chunk(c, _):
        out_ref[:, pl.ds(pl.multiple_of(c * r, r), r)] = jnp.zeros(
            (r, r), jnp.int8
        )
        return _

    jax.lax.fori_loop(i + 1, seq // r, zero_chunk, 0)  # above the diagonal

    @pl.when(first + r <= top_k)
    def _keep_every_valid_key():
        def chunk(c, _):
            out_ref[:, pl.ds(pl.multiple_of(c * r, r), r)] = jnp.ones(
                (r, r), jnp.int8
            )
            return _

        jax.lax.fori_loop(0, i, chunk, 0)
        for s in range(strips):
            out_ref[:, pl.ds(tile_at(i, s), lanes)] = causal(0, s, r).astype(
                jnp.int8
            )
        tie_ref[n, i] = 0

    @pl.when(first + r > top_k)
    def _select():
        for j in range(j_heads):  # head weights: a row [1, R] -> a tile
            wb_ref[j] = _row_tile(w_ref[j], lanes)
        scale = (di * j_heads) ** -0.5

        def scores(at):
            """A strip's index scores [R, lanes] float32 as ordered keys."""
            keys = _key_slots(ki_ref[pl.ds(at, lanes), :], g)
            index = None
            for c in range(j_heads // g):
                q = qi_ref[:, c * plan.window:(c + 1) * plan.window]
                for u in range(g):
                    term = jnp.maximum(_dot(q, keys[u], 1, 1), 0.0) * wb_ref[
                        c * g + u
                    ]
                    index = term if index is None else index + term
            return _signed_keys(index * scale)

        def score_chunk(c, _):
            for s in range(strips):
                at = tile_at(c, s)
                keys_ref[:, pl.ds(at, lanes)] = scores(at)
            return _

        jax.lax.fori_loop(0, i, score_chunk, 0)
        for s in range(strips):  # the diagonal: later keys take the lowest
            at = tile_at(i, s)
            keys_ref[:, pl.ds(at, lanes)] = jnp.where(
                causal(0, s, r), scores(at), jnp.int32(_LOWEST)
            )

        def strip(at_strip, tied):
            rows = _at(at_strip, rb)
            zero = jnp.zeros((rb, lanes), jnp.int32)

            def valid(s):
                return causal(at_strip * rb, s, rb)

            def walk(body, carry, diagonal: bool = False):
                """``carry = body(keys [rows, lanes], chunk, strip, valid,
                carry)`` over the block's chunks: ``valid`` None — or, with
                ``diagonal``, in the diagonal chunk, its causal mask (below
                it every key is valid)."""
                def chunk(c, carry, mask=lambda s: None):
                    for s in range(strips):
                        carry = body(
                            keys_ref[rows, pl.ds(tile_at(c, s), lanes)], c, s,
                            mask(s), carry,
                        )
                    return carry

                if not diagonal:
                    return _for_chunks(i + 1, unroll, chunk, carry)
                return chunk(i, _for_chunks(i, unroll, chunk, carry), valid)

            def across(count):  # lane-partial -> the row's, in every lane
                return jnp.broadcast_to(
                    jnp.sum(count, axis=-1, keepdims=True), count.shape
                )

            def count_of(hit):
                return across(walk(
                    lambda keys, c, s, _valid, count: count + hit(
                        keys, c, s
                    ).astype(jnp.int32), zero,
                ))

            # tau: the largest u, built from the top bit down, with at
            # least k keys >= u (the lowest, u = 0 unsigned, always has; a
            # position that is not valid holds the lowest and counts for no
            # other u). ``held``: the keys >= the u found so far. Once every
            # row of the strip holds EXACTLY k, {keys >= found} is each
            # row's top-k whatever bits of tau are still unknown (tau lies
            # between found and the k-th key; the next key is below found):
            # the passes left would select the same keys, and no key is
            # tied across the threshold
            def bit(state):
                b, found, held, _ = state
                candidate = found ^ (jnp.int32(1) << (31 - b))
                count = count_of(lambda keys, _c, _s: keys >= candidate)
                enough = count >= top_k
                held = jnp.where(enough, count, held)
                return (b + 1, jnp.where(enough, candidate, found), held,
                        jnp.min((held == top_k).astype(jnp.int32)))

            _, tau, _, exact = jax.lax.while_loop(
                lambda state: (state[0] < 32) & (state[3] == 0), bit,
                (jnp.int32(0), jnp.full((rb, lanes), _LOWEST, jnp.int32),
                 zero, jnp.int32(0)),
            )

            def write(taken):
                """The strip's rows of the block: ``taken(keys, chunk,
                strip)`` at the valid keys."""
                def store(keys, c, s, valid, _):
                    chosen = taken(keys, c, s)
                    if valid is not None:
                        chosen &= valid
                    out_ref[rows, pl.ds(tile_at(c, s), lanes)] = (
                        chosen.astype(jnp.int8)
                    )
                    return _

                walk(store, 0, diagonal=True)

            def tied_rows():
                """All 32 passes ran: tau IS the k-th key. 1 where a row
                has more keys equal to it than it may take — the strip then
                takes the first of them by position here —, else 0."""
                def counts(keys, _c, _s, valid, carry):
                    above, equals = carry
                    equal = keys == tau
                    if valid is not None:
                        equal &= valid
                    return (above + (keys > tau).astype(jnp.int32),
                            equals + equal.astype(jnp.int32))

                carry = walk(counts, (zero, zero), diagonal=True)
                need = top_k - across(carry[0])  # of the equal keys: >= 1
                excess = jnp.max((across(carry[1]) > need).astype(jnp.int32))

                @pl.when(excess > 0)
                def _the_first_equal_keys():
                    def position(c, s):
                        return c * r + s * lanes + jax.lax.broadcasted_iota(
                            jnp.int32, (rb, lanes), 1
                        )

                    query = first + at_strip * rb + jax.lax.broadcasted_iota(
                        jnp.int32, (rb, lanes), 0
                    )

                    def equal(keys, c, s):
                        return (keys == tau) & (position(c, s) <= query)

                    # the last equal key a row takes: the largest p with
                    # fewer than ``need`` equal keys BEFORE it
                    bits = max(seq - 1, 1).bit_length()

                    def bit(b, found):
                        candidate = found | (jnp.int32(1) << (bits - 1 - b))
                        fewer = count_of(
                            lambda keys, c, s: equal(keys, c, s)
                            & (position(c, s) < candidate)
                        ) < need
                        return jnp.where(fewer, candidate, found)

                    last = jax.lax.fori_loop(0, bits, bit, zero)
                    write(lambda keys, c, s: (keys > tau) | (
                        equal(keys, c, s) & (position(c, s) <= last)
                    ))

                return excess

            excess = jax.lax.cond(exact > 0, lambda: jnp.int32(0), tied_rows)

            @pl.when(excess == 0)
            def _every_key_at_or_above():
                write(lambda keys, _c, _s: keys >= tau)

            return jnp.maximum(tied, excess)

        tie_ref[n, i] = _loop(r // rb, strip, jnp.int32(0))


@functools.partial(jax.jit, static_argnums=(0, 2), inline=True)
def _select_call(plan: _Plan, operands, interpret):
    q_index = operands[0]
    b = q_index.shape[0]
    seq, _k, j_heads, di, _g, r, lanes = plan[:7]
    size = q_index.dtype.itemsize
    return pl.pallas_call(
        functools.partial(_select_kernel, plan=plan),
        grid=(b, seq // r),
        in_specs=[
            pl.BlockSpec((None, r, j_heads * di), lambda n, i: (n, i, 0)),
            # the key head of the whole batch row: resident
            pl.BlockSpec((None, seq, di), lambda n, i: (n, 0, 0)),
            pl.BlockSpec((j_heads, 1, r), lambda n, i: (n, 0, i)),
        ],
        out_specs=[
            pl.BlockSpec((None, r, seq), lambda n, i: (n, i, 0)),
            pl.BlockSpec(memory_space=pltpu.SMEM),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((b, seq, seq), jnp.int8),
            jax.ShapeDtypeStruct((b, seq // r), jnp.int32),
        ],
        scratch_shapes=[
            pltpu.VMEM((j_heads, r, lanes), jnp.float32),
            pltpu.VMEM((r, seq), jnp.int32),  # the block's ordered keys
        ],
        interpret=interpret,
        name="index_select",
        compiler_params=_vmem(
            r * seq * (4 + 2)  # the keys; the output block, twice
            + 2 * size * (seq * max(di, STATE_LANES) + r * j_heads * di)
            + 4 * j_heads * r * (lanes + 2 * 8)
        ),
    )(*operands)


def index_select(q_index, k_index, weights, top_k: int,
                 block_rows: int = BLOCK_ROWS,
                 interpret: Optional[bool] = None):
    """(the selection, int8 [B, S, S] — rows queries: 1 at the ``top_k``
    keys s <= t with the largest index score of each query t, all of them
    where t < top_k, ties to the lower s, 0 elsewhere —, [B, S / R] int32: 1
    where a block of R query rows ran the bisection over the position, a row
    of it holding more keys equal to its threshold than it may take) of
    q_index [B, S, J, D_I], k_index [B, S, D_I] (one key head) and weights
    [B, S, J]: the scores are ``models/keye_vl2.index_scores``'s, made and
    ranked in VMEM. No gradient."""
    if interpret is None:
        interpret = pallas_interpret()
    q_index, k_index, weights = jax.lax.stop_gradient(
        (q_index, k_index, weights)
    )
    b, seq, j_heads, di = q_index.shape
    rows = _pick_block(seq, block_rows)
    plan = _Plan(
        seq, top_k, j_heads, di, _heads_per_block(j_heads, di, di), rows,
        _pick_block(rows, STATE_LANES), _pick_block(rows, BISECT_ROWS),
        CHUNKS_A_STEP,
    )
    return _select_call(plan, (
        q_index.reshape(b, seq, -1), k_index,
        weights.astype(jnp.float32).transpose(0, 2, 1).reshape(
            b * j_heads, 1, seq
        ),
    ), interpret)
