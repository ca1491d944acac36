"""Fused flash attention as a Pallas TPU kernel (forward + backward).

The hot op of the ALBERT workload (AlbertSelfAttention, models/albert.py) and
the long-context path. Same exact-softmax math as FlashAttention: the S×S
score matrix never leaves VMEM — logits for one (query-block, kv-block) tile
are computed on the MXU, folded into an online-softmax accumulator, and
discarded. HBM traffic per head drops from O(S²) (XLA's unfused dense path
materializes probs for the backward) to O(S·D + S).

Kernel structure (the canonical Pallas flash shape): the reduction axis is
the INNERMOST GRID DIMENSION, not an in-kernel loop over a resident slab —
TPU grids execute sequentially, so the online-softmax state (acc, m, l) lives
in VMEM scratch across the inner iterations, initialized at the first and
flushed to the output block at the last. The forward's VMEM use is O(block),
independent of S: its sequence length is bounded by HBM, not VMEM (verified
S=16k on a v5e). The BACKWARD's is O(S): see "The tiled backward".

Backward follows the standard flash recipe: save only (out, logsumexp) as
residuals and recompute probability tiles on the fly — in ONE kernel a call,
on the forward's walk, in both forms (``_bwd_fused`` where a single tile
covers the sequence, ``_bwd_tiled`` else) — with delta = rowsum(dO ⊙ O)
taken inside the kernel from the dO and O blocks it loads.

The tiled backward. ONE sweep (``_bwd_tiled_kernel``, ``flash_*_bwd_tiled``
in a device trace): grid (B, kv block, the kv block's query programs —
``members``: the programs of a group that share its kv head; one where k has
as many heads as q —, query tile, a step of the key sweep), the query tile
outer and the key sweep inner exactly as the forward walks them (``_k_tile``,
``_sweep``, ``_for_k_step``: the steps above the diagonal, before a band,
past the block rule's runs, and a selection's empty tiles run no body). q,
dO, O and lse stay resident over a sweep; k, v, the bias and a selection's
tile are the inner fetch. A tile's s, p = exp(s - lse), dp, delta and ds are
made ONCE a head (``_backward_heads``) and feed three products: dq += ds·k
into a [Bq, heads·d] float32 scratch zeroed at a sweep's first step and
flushed at its last; dv += pᵀ·dO and dk += dsᵀ·q into float32 accumulators
that hold the kv block's dk and dv for the WHOLE sequence, [S, kv block·d]
and [S, kv block·dv], added at the rows of the step's key tile, zeroed at
the kv block's first step and cast and written once at its last, through
output blocks that are the whole sequence too. That residency is what lets
dq and dk / dv share a walk: no read-modify-write of HBM, no per-key-tile
partial dq. 5 matmuls, one ``exp``, one mask pass and one fetch stream a
tile, where the two kernels this replaced (``*_bwd_dq`` over query tiles,
``*_bwd_dkv`` over key tiles, each rebuilding the tile: until PR 56) spent
7, 2, 2 and 2. The members' axis lies OUTSIDE the query tile's, so a key
tile's rows meet their terms in the order the dkv kernel summed them (a
group's first program over every query tile, then its second). What bounds
S now is VMEM (``_bwd_vmem``): 4 + 4 bytes a row and lane of the kv block
(the accumulators; the output blocks' two buffers in bf16) beside a step's
blocks and a program's transients — 16 + 16 MiB at S=16,384 and one kv head
of 128, and the call asks for its scoped limit (84 MiB at Keye's shape,
76.75 for SmallThinker's seven heads a program, 53.5-71 at the other
cells'; a v5e core has 128); a call whose ask passes 112 MiB at ONE column
block a program — S=53,248 at those widths — is refused with an error that
says so. A call on a v5e, the pair → the sweep, ms (PR 56; each mode alone,
``tools/chip_gqa_check.py`` / ``chip_causal_check.py`` /
``chip_mla_check.py``; two heads a program then, except the groups of seven
and six): selected, 32 / 4 x 128 at S=16,384, 26.02 + 30.33 → 38.19; band
4,096 and causal at 28 / 4 x 128, S=16,384 (seven heads a program), 8.04 +
10.67 → 13.12 and 17.19 + 22.38 → 27.88; the block rule, 32 / 4 x 128 over
2 x 4,096, 3.95 + 5.18 → 5.99; band 512 at 64 / 8 x 128 and causal at 48 /
8 x 128 (six heads a program), S=8,192, 2.76 + 3.45 → 4.27 and 7.95 + 9.97
→ 12.77; 32 / 8 x 64 at S=4,096, 1.63 + 2.16 → 2.50; 16 x 128 causal at
S=4,096, 0.76 + 0.99 → 1.28; 32 x 192 / 128, 2.54 + 2.81 → 3.82: 0.66-0.73
of the pair in every mode, 1.22-1.36 x the dkv kernel alone, so ONE path
serves every tiled call and no shape keeps the pair.

Heads a program. ONE rule, forward and backward (``_heads_a_program``): a
tiled call's program takes the MOST query heads — whole column blocks that
divide the head count, eight at most (``_head_plans``) — whose scoped-VMEM
ask (``_fwd_ask`` / ``_bwd_ask``) leaves an eighth of the 112 MiB ceiling
free; the last plan, one column block, may ask up to the ceiling. A grouped
program's kv block is whole column blocks of kv heads, so a group of eight
or fewer is ONE program — k / v, the bias and a selection's tile fetched
once a group, dk / dv summed inside the program, no ``members`` — a group
of seven or six as one of eight (no branch of its own: seven has no divisor
between itself and one), sixteen heads over one kv head two programs of
eight that share the kv block, four heads a kv head of 64 the eight heads
over a column block's two kv heads. With as many kv heads as heads the kv
block is the program's own heads and dk / dv, resident for the sequence,
grow with them: the backward takes 4 of 16 x 128 at S=4,096 (61 MiB; eight
would ask 118), 4 of 32 x 192 / 128 at 4,096 (71), 2 at 8,192 (57.5; four:
111), while the forward, which holds nothing for the sequence, takes eight
everywhere (21-32 MiB). A longer row gives heads up before it is refused:
32 / 4 x 128 takes 8 at S=16,384, 4 at 32,768, 2 at 40,960, 1 at 49,152.
Nothing but shapes enters — group, widths, S, tiles: no argument, no
environment, no model's name. The forward's out and lse are the same bits
at every count (no sum crosses heads), dq too; dk / dv of a grouped call
move in float32's late digits with the order a group's heads meet in
(2-4e-5 relative, on the chip). Until PR 58 the count was a budget from the
days of a 16 MiB scoped limit — 6 MB of score tiles forward, 4 backward:
four and two heads at 512 x 512 — halved until the kv heads divided, with
a group of seven special-cased whole. A call alone on a v5e at the old
count → the new, ms (PR 58, the three chip checks with ``--at-most-heads 4
2``), forward | backward: selected 32 / 4 x 128 at S=16,384, 15.63 → 14.22
(73.6 → 81.0 % of its roofline) | 38.18 → 32.22 (75.4 → 89.3 %); the block
rule 32 / 4 x 128 over 2 x 4,096, 2.47 → 2.29 | 6.01 → 5.13 (72.6 → 85.0);
band 512 at 64 / 8 x 128, S=8,192, 2.02 → 1.92 | 4.27 → 3.78 (79.2 →
89.5); causal 16 / 1 x 128 at 8,192, 1.85 → 1.79 | 4.57 → 4.09 (81.1 →
90.6); 32 / 8 x 64 at 4,096, 1.22 → 1.13 | 2.50 → 2.19; 16 x 128 at 4,096,
0.540 → 0.526 | 1.284 → 1.204 (76.4 → 81.5); 32 x 192 / 128 at 4,096, 1.51
→ 1.45 | 3.82 → 3.69; at 8,192, 5.62 → 5.36 | 14.12 (two heads, as it
was). One head a program, the other end, ran the backward kernels of the
time at 63 / 68 % against seven heads' 90 / 90 (28 / 4 x 128 at S=16,384,
PR 36). Eight heads' larger asks leave XLA less fast memory for what it
places around a call (Ouro's accumulate step: 47 MB more scratch in HBM).

One-tile forms. When one (Bq, Bk) tile covers the sequence (``_pick_block``
gives S for both blocks: S=512 under the default 512, every tiny test
model) both directions take a kernel written for that case — the forward
(``_fwd_one_tile``) as the backward (``_bwd_fused``): grid (B, programs
across the width), no scratch, no ``pl.when`` phases. The shapes decide,
nothing else does. A row's softmax is complete after its one tile, so the
one-tile forward keeps no running max or sum, corrects nothing by
exp(m_prev - m_new) and accumulates nothing across steps; it is the tiled
kernel's arithmetic in the same order (there the correction is exactly 0 and
the accumulator exactly p·v), so ``out`` and ``lse`` are the same bits, and
0.21 ms a call against 0.40 at (12, 512, 16 x 64) on a v5e for the tiled
kernel as it was then (PR 26).

The tiled form's state. The running max ``m`` and denominator ``l`` of a
head are TILES [Bq, 128] of float32 scratch with the row's value in every
lane (``STATE_LANES``), beside the [Bq, H·Dv] accumulator. A row reduce
leaves its result in every lane already, so ``m_new`` meets ``m_prev``,
``s`` and the accumulator register against register: no lane permute, no
masked store, and ``corr = exp(m_prev - m_new)`` IS the accumulator's
multiplier where a head owns its lane tile (a select by lane where two
share one, D=64). It is the same arithmetic in the same order as with
[Bq, 1] columns — the same bits, on the CPU and on the v5e — and what it
buys is not the permutes' own time: as columns, the state chained a
program's heads (head i+1's q·kᵀ did not start under head i's softmax: the
compiled body ran the MXU full for ~500 bundles a head and a quarter full
for ~1,300 more, no unit saturated); as tiles the heads overlap and the
body's bundles are 88-90 % MXU at D=128 and 192 / 128, 74 % at D=64 (the
store slot fills first there). A call on a v5e, forward alone, columns →
tiles (PR 37): 1.132 → 0.594 ms at (1, 4096, 16 x 128) causal, 35 → 66 % of
its roofline; 12.55 → 5.94 and 26.08 → 12.35 ms at (1, 16384, 28 / 4 x 128),
band 4,096 and causal, 38 → 81 %; 2.421 → 1.637 ms at 32 x 192 / 128, 41 →
60 % (its ceiling 83); 1.869 → 1.237 ms at 32 / 8 x 64, 21 → 32 % (ceiling
50). What it still pays for: the tile's ``exp`` and two row reduces, the
accumulator's read-modify-write a key tile, the init and flush of a query
tile (794 + 1,252 bundles beside 4,500 a key tile, four heads of 128 a
program), and at D=64 the lanes zeroed for the other head. Skipping the
correction on a tile where no row's max rose was built and measured: 0.693
ms where this reads 0.594 (holding p·v across a branch costs more than the
multiply it saves, and the body is MXU-bound), and at random weights no
tile of any cell's shape takes the skip. With nothing ordering the heads
the scheduler holds every head's score tile on its stack at once, so a
program of more than two heads of 128 asks for its scoped VMEM
(``_fwd_vmem``).

The softmax scale stays a multiply on the float32 scores in both forms:
folding it into q where 1/sqrt(D) is a power of two (exact: the same bits)
was measured on the chip and is not faster (0.212 ms against 0.209), nor is
a reciprocal-and-multiply in place of the one divide.

Layout contract: ONE layout, the model's. q, k, v, dO go in and out, dq, dk,
dv come out as [B, S, H·D], the array a dense projection writes and the
out-projection reads ([B, S, H, D] at the public entry is the same bytes);
no transpose on either side, so what the remat policy stashes is what the
kernels read. A BlockSpec block is (1, block, W) at (b, j, p): a COLUMN
BLOCK of g adjacent heads, g = the fewest whose g·D lanes fill whole
128-lane tiles (2 at D=64, 1 at D=128, 2 at q/k 192 beside v 128) — or the
whole width H·D where the head count does not divide into such blocks (the
tiny test models). Inside a block every per-head product contracts over,
and lands in, the head's own lane WINDOW (``_window``): the smallest run of
whole 128-lane tiles that covers the head's lanes — the block itself at
D=64 (both heads) and D=128, lanes 0-255 / 128-383 of a 384-lane block at
192, the head's own tile of v, dO and out at 128 x 2. What a window holds
of another head (all of it at D=64, 64 lanes of 256 at 192, nothing where
the window is the head) is zeroed on one operand of each product
(``_only_head``): the dropped terms are products with exact zeros, so the
MXU spends no pass on a lane tile the head does not touch. Windows overlap
where heads share a tile (lanes 128-255 at 192): accumulators and outputs
are read and written by the aligned SEGMENTS between window edges
(``_segments``), each head's product added into the segments of its window
(``_into``). A call whose windows are narrower than its blocks says so in
its kernel ``metadata`` (``_lanes``). A program takes several column
blocks (see "Heads a program"; a one-tile call ``_pick_heads``' budget);
batch is a grid axis. Per-position scalars ride
as ROW vectors — the additive bias [B, 1, S], lse [B·H, 1, S]: a [.., S, 1]
column layout would be 128×-padded by the TPU's (8, 128) tiling — 2 GB of
HBM for S=16k — so rows travel packed and are transposed to columns in
VMEM where the math needs them. The bias is per KV position (0 keep / -inf
drop), the same for every head — exactly the mask bias AlbertModel builds;
it is non-differentiable (it comes from the attention mask). A decoder's
causal mask is a MODE of the same kernels (``causal=True``; see "causal
tiles" below): tiles above the diagonal are neither fetched nor computed,
tiles the diagonal crosses get an iota mask, and the kernels are named
``flash_causal_*`` in a device trace. A BAND (``band=w``: a sliding window,
query i sees keys i-w+1 .. i) is the causal mask with a second edge: tiles
wholly before the band are not even grid steps — the inner grid axis is as
long as the band's tiles and sweeps from the outer tile's first needed tile
— the tiles its lower edge crosses get the iota mask too, and the kernels
are named ``flash_band_*``. What a query may see is ONE description
(``_Mask``: causal, band) that the index maps, the sweep's length, the tile
cases, the iota mask, the names and the count of visited tiles all read.
GROUPED-QUERY attention is read from the shapes: k and v with fewer heads
than q stay that narrow in HBM, forward and backward, and dk / dv are summed
over a group inside the kernel (see "grouped-query heads" below;
``flash_gqa_*`` in a device trace). BLOCK DIFFUSION (``block_diffusion=B``)
is the third field of the same description: the sequence is a NOISY copy of
a row followed by the CLEAN one, both cut into blocks of B positions, and a
query sees the clean blocks before its own (a clean query: its own too) and,
where it is noisy, the noisy keys of its own block — keys AFTER it among
them. A tile then needs up to TWO runs of tiles of the other axis (see
"block-diffusion tiles" below); the kernels are named ``flash_bd_*``.

Off-TPU (CPU tests, CI) the same kernels run under ``interpret=True``
(``utils.backend.pallas_interpret`` decides, once, for every op here).

On a multi-device mesh a Mosaic kernel cannot be partitioned by GSPMD, so
``flash_attention(..., mesh=...)`` runs the custom-VJP op under
``jax.shard_map``: batch over "data", heads over "model" where the mesh has
that axis (a shard is [B/dp, S, (H/tp)·D]), the sequence whole on every
device.
"""
from __future__ import annotations

import collections
import functools
import math
from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.ad_checkpoint import checkpoint_name
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu
from jax.sharding import Mesh, PartitionSpec as P

from dedloc_tpu.utils.backend import pallas_interpret

NEG_INF = -1e30


def mesh_axis(mesh: Mesh, name: str) -> Optional[str]:
    """``name`` if the mesh has that axis (shard over it), else None."""
    return name if name in mesh.axis_names else None


def _pick_block(s: int, preferred: int) -> int:
    block = min(preferred, s)
    while s % block:
        block //= 2
    return max(block, 1)


def _one_tile(s: int, block_q: int, block_k: int) -> bool:
    """One (Bq, Bk) tile covers the whole sequence: forward and backward
    then take their one-tile kernels (see "One-tile forms")."""
    return _pick_block(s, block_q) == s and _pick_block(s, block_k) == s


def _t(x):
    """2D transpose (row [1, N] <-> column [N, 1] relayout in VMEM)."""
    return jnp.swapaxes(x, -1, -2)


# --------------------------------------------- heads inside a column block


_MOST_HEADS = 8  # of one program, in any call


def _heads_per_block(h: int, d: int, dv: int) -> int:
    """Adjacent heads that share one column block: the fewest whose lanes
    fill whole 128-lane tiles at BOTH widths (2 at D=64, 1 at D=128, 2 at
    192 / 128), or all ``h`` heads — the whole width, the one other block
    Mosaic takes — when those do not divide the head count (the tiny test
    models, an odd head count)."""
    g = max(128 // math.gcd(128, d), 128 // math.gcd(128, dv))
    return g if h % g == 0 else h


def _pick_heads(h: int, g: int, block_q: int, block_k: int,
                budget_mb: float) -> int:
    """Heads per program of a ONE-TILE call, in whole column blocks of
    ``g``: amortise grid-step overhead while keeping the per-head transient
    (fp32 scores + bf16 probs ≈ 6·Bq·Bk bytes) within a conservative VMEM
    budget (the compiler's own scoped limit: 16 MiB a core on a v5e). A
    TILED call asks for its VMEM and takes what that holds
    (``_heads_a_program``)."""
    per_head_mb = 6.0 * block_q * block_k / 2**20
    n = max(1, _MOST_HEADS // g)
    while n > 1 and ((h // g) % n or n * g * per_head_mb > budget_mb):
        n //= 2
    return n * g


def _geometry(q, d: int, dv: int, block_q: int, block_k: int):
    """(B, S, H, heads per column block, Bq, Bk) for [B, S, H·D] operands
    (``q``: [B, S, H·d]; v and out are H·dv wide)."""
    b, s, width = q.shape
    h = width // d
    return (b, s, h, _heads_per_block(h, d, dv), _pick_block(s, block_q),
            _pick_block(s, block_k))


def _column_blocks(width: int, g: int, d: int, dv: int):
    """(first head, q/k lane slice, v/out lane slice) of each column block
    of a program's tiles: [N, width] for q and k (heads ``d`` wide),
    [N, width / d · dv] for v, out and dO. One slice twice where the two
    widths are equal."""
    w, wv = g * d, g * dv
    return [
        (c * g, slice(c * w, (c + 1) * w), slice(c * wv, (c + 1) * wv))
        for c in range(width // w)
    ]


def _window(i: int, width: int, g: int) -> slice:
    """Head i's lane WINDOW inside a column block of ``g`` heads ``width``
    wide: the smallest run of whole 128-lane tiles that covers the head's
    lanes — what its products contract over and land in — or the whole
    block where the block is not whole lane tiles (the tiny test models).
    The block itself at 64 x 2 (both heads) and 128 x 1; lanes 0-255 and
    128-383 at 192 x 2; the head's own tile, 0-127 / 128-255, at 128 x 2."""
    if (g * width) % 128:
        return slice(0, g * width)
    return slice(i * width // 128 * 128, -(-(i + 1) * width // 128) * 128)


def _segments(width: int, g: int):
    """A column block's lanes cut at every window edge: the aligned pieces
    an accumulator or an output is read and written by, each covered whole
    by every window that touches it. One piece, the block, where the window
    is the block; the three lane tiles at 192 x 2 (heads 0 | 0 and 1 | 1)."""
    windows = [_window(i, width, g) for i in range(g)]
    edges = sorted({edge for w in windows for edge in (w.start, w.stop)})
    return [slice(a, b) for a, b in zip(edges, edges[1:])]


def _at(block: slice, part: slice) -> slice:
    """``part`` of a column block, as lanes of the program's tile."""
    return slice(block.start + part.start, block.start + part.stop)


def _segments_at(block: slice, width: int, g: int):
    return [_at(block, seg) for seg in _segments(width, g)]


def _per_window(width: int, g: int, make):
    """[``make(head i's window)`` for the g heads]: made once per distinct
    window, so heads that share one (D=64) share its loads."""
    windows = [_window(i, width, g) for i in range(g)]
    made = {}
    for w in windows:
        if (w.start, w.stop) not in made:
            made[w.start, w.stop] = make(w)
    return [made[w.start, w.stop] for w in windows]


def _only_head(x, i: int, width: int, g: int):
    """``x`` [N, head i's window] with what the window holds of the other
    heads zeroed (64 lanes of 256 at 192 x 2; nothing where the window is
    the head: D=128, and v / dO / out at 128 x 2). Contracting over the
    window then gives head i's product exactly (the other terms are zeros),
    and a product WITH it lands in head i's lanes of the window and nowhere
    else — so the heads of a block sum into tiles that are already in the
    model's layout. Windows are whole lane tiles: cutting one out of a
    block is a choice of vector registers. Cutting a HEAD out where it is
    half a lane tile (D=64: lane slices and a concatenate) is a relayout,
    and measured 5-10 % slower on a v5e than the zeroing."""
    if x.shape[-1] == width:
        return x
    lo = i * width - _window(i, width, g).start
    lane = jax.lax.broadcasted_iota(jnp.int32, x.shape, 1)
    return jnp.where((lane >= lo) & (lane < lo + width), x,
                     jnp.zeros_like(x))


def _into(totals, i: int, width: int, g: int, term) -> None:
    """Add head i's product ``term`` [N, its window] into ``totals``, one
    running sum per segment of the block (None: nothing yet)."""
    w = _window(i, width, g)
    for n, seg in enumerate(_segments(width, g)):
        if w.start <= seg.start and seg.stop <= w.stop:
            whole = (seg.start, seg.stop) == (w.start, w.stop)
            piece = term if whole else term[
                :, seg.start - w.start:seg.stop - w.start
            ]
            totals[n] = piece if totals[n] is None else totals[n] + piece


STATE_LANES = 128  # a register's lanes: the width of a row's state tile


def _across(x, width: int):
    """``x`` [N, L] with ONE value a row in all its L lanes, ``width`` lanes
    wide: whole copies of its registers side by side (or the first lanes of
    them) — no lane moves. A column [N, 1] is returned as it is, to
    broadcast."""
    lanes = x.shape[-1]
    if lanes in (1, width):
        return x
    if width < lanes:
        return x[:, :width]
    if width % lanes:
        return jnp.broadcast_to(x[:, :1], (x.shape[0], width))
    return pltpu.repeat(x, width // lanes, axis=1)


def _per_head_lanes(columns, width: int, seg: slice):
    """The g per-head values — columns [N, 1], or tiles [N, L] that hold
    the value in every lane — across lanes ``seg`` of their column block,
    head i's over head i's lanes (to scale an accumulator head by head):
    the column itself, to broadcast, or the tile's registers as they are,
    where one head owns the segment (its own tile: D=128, and v's 128 x 2),
    else a select by lane."""
    heads = [
        i for i in range(len(columns))
        if i * width < seg.stop and (i + 1) * width > seg.start
    ]
    lanes = seg.stop - seg.start
    out = _across(columns[heads[-1]], lanes)
    if len(heads) > 1:
        lane = jax.lax.broadcasted_iota(
            jnp.int32, (out.shape[0], lanes), 1
        )
        for i in reversed(heads[:-1]):
            out = jnp.where(
                lane < (i + 1) * width - seg.start,
                _across(columns[i], lanes), out,
            )
    return out


def _lanes(d: int, dv: int, g: int) -> Optional[dict]:
    """What a kernel's shapes chose, for its ``metadata``: the lanes a
    head's products contract over and land in (its window) and the lanes of
    a column block, at both widths — where a window is narrower than its
    block; None where every window is the block (D=64, D=128). A lowered
    module carries it (tools/tpu_aot.py prints it per program as
    ``flash_windows``). None, not "window == block", because metadata is
    not free: XLA:TPU sees it as frontend attributes of the custom call and
    schedules the program AROUND the call differently (Ouro's
    accumulate_step: 14 ``copy`` instructions in its scanned bodies with
    it, 19 without), and the equal-width callers keep the programs they
    had."""
    qk, v = _window(0, d, g), _window(0, dv, g)
    lanes = {
        "qk_window": qk.stop - qk.start, "qk_block": g * d,
        "v_window": v.stop - v.start, "v_block": g * dv,
    }
    whole = (lanes["qk_window"], lanes["v_window"]) == (g * d, g * dv)
    return None if whole else lanes


def _metadata(d: int, dv: int, g: int, q, k, mask,
              hp: Optional[int] = None) -> Optional[dict]:
    """A call's kernel ``metadata``: its windows where they are narrower
    than its blocks (``_lanes``), its head counts where k has fewer than q,
    its band or its blocks where it has them; None for every other call
    (see ``_lanes`` for why not more). Beside those, the query heads ONE
    PROGRAM of a tiled call takes (``hp``: ``heads_a_program``, what
    ``_heads_a_program`` chose) — except where the program is one whole
    group of a grouped call, which ``heads`` / ``kv_heads`` say already, so
    the calls that were whole groups before the count followed VMEM keep
    their programs; and not as the ONLY field, for ``_lanes``' reason (in
    Ouro's accumulate step it cost 47 MB of scratch the cell does not
    have: there ``flash_vmem_mb`` says the count)."""
    h, kvh = q.shape[-1] // d, k.shape[-1] // d
    found = _lanes(d, dv, g) if kvh == h else {"heads": h, "kv_heads": kvh}
    if mask.band is not None:
        found = dict(found or {}, band=mask.band)
    if mask.blocks is not None:
        found = dict(found or {}, block=mask.blocks.length,
                     stream=mask.blocks.stream)
    if found and hp is not None and hp * kvh != h:
        found = dict(found, heads_a_program=hp)
    return found


def _dot(a, b, contract_a: int, contract_b: int):
    return jax.lax.dot_general(
        a, b, (((contract_a,), (contract_b,)), ((), ())),
        preferred_element_type=jnp.float32,
    )


# ----------------------------------------------------- grouped-query heads
#
# With fewer key/value heads than query heads (``group`` = H / H_kv query
# heads read one kv head) k and v stay at their own width in HBM, forward
# and backward: a program's kv BLOCK is the column block(s) of k / v that
# hold its query heads' kv heads, and a query column block takes its ONE kv
# head out of it (the g heads of a column block share a kv head: ``group``
# is a multiple of g). At D=128 that kv head is a lane tile of its own. At
# D=64 it is one HALF of a 128-lane tile while the block's two query heads
# sit in both halves, so the tile is rebuilt with the kv head in BOTH
# (``_group_tile``: one lane rotation by 64 and a select, once per kv head
# and tile, shared by every query head of the group in the program); the
# per-head products then zero the other head's half exactly as the
# equal-count kernels do. dK / dV: the heads of a column block sum into the
# block's tile slot by slot, the tile is folded onto the kv head's half
# (``_fold_group``) and added, at the key tile's rows, to the kv block's
# float32 accumulator, which lives across the group's query programs — the
# ``members`` axis of the backward's grid — and is written once per kv head.


def _group(q, k, d: int, dv: int, g: int) -> int:
    """Query heads a kv head of a call (1: as many kv heads as heads). A
    grouped call's column block of ``g`` heads is whole lane tiles and
    shares one kv head."""
    h, kvh = q.shape[-1] // d, k.shape[-1] // d
    if kvh == h:
        return 1
    group = h // kvh
    if (d != dv or h % kvh or g > 2 or (g * d) % 128 or kvh % g
            or group % g):
        raise ValueError(
            f"grouped-query attention takes {h} query heads over {kvh} kv "
            f"heads of {d} only where a column block of {g} heads is whole "
            "lane tiles and shares one kv head (head widths 64 and 128)"
        )
    return group


def _head_plans(h: int, group: int, g: int):
    """Every (query heads a program, kv heads a kv block) a tiled call of
    ``h`` heads in groups of ``group`` may take, most heads first: whole
    column blocks of ``g`` that divide the head count, ``_MOST_HEADS`` at
    most. With as many kv heads as heads the kv block is the program's own
    heads. A grouped program's kv block is whole column blocks (``g`` kv
    heads at least) that divide the kv heads: the kv heads of a program
    that spans whole groups, else ONE column block that ``g·group / heads``
    programs share (the backward's ``members``) — so a group of eight or
    fewer is one program (seven, six: no divisor between them and one),
    sixteen heads over one kv head are two of eight."""
    plans = []
    for n in range(max(1, min(_MOST_HEADS // g, h // g)), 0, -1):
        hp = n * g
        kvb = hp if group == 1 else max(g, hp // group)
        if (h % hp == 0 and (h // group) % kvb == 0 and kvb % g == 0
                and (kvb * group) % hp == 0):
            plans.append((hp, kvb))
    return plans


# what a core's VMEM can be asked for (a v5e's is 128 MiB; the compiler's own
# scoped limit is 16): the ceiling of a tiled call, and of it what a program
# of more than the fewest heads leaves free — the transients' figures are the
# scheduler's habits at two and four heads, not a bound on it at eight
_VMEM_CEILING = 112 * 2**20
_VMEM_MARGIN = _VMEM_CEILING // 8


def _heads_a_program(plans, ask):
    """The (heads a program, kv heads a kv block) of a tiled call: the
    first of ``plans`` (most heads first) whose ``ask(heads, kv heads)`` —
    the scoped VMEM the call would ask for, bytes — leaves the margin under
    ``_VMEM_CEILING``; the last, one column block, whatever it asks (the
    backward refuses what passes the ceiling: ``_bwd_vmem``). The count
    follows from the call's shapes alone — group, widths, S, tiles."""
    for hp, kvb in plans[:-1]:
        if ask(hp, kvb) <= _VMEM_CEILING - _VMEM_MARGIN:
            return hp, kvb
    return plans[-1]


def _tiled_geometry(q, k, d: int, dv: int, block_q: int, block_k: int, ask):
    """(B, S, H, heads per column block, heads per program, Bq, Bk, group,
    kv heads per kv block) of a tiled call; ``ask(heads, kv heads, Bq,
    Bk)``: the direction's VMEM ask."""
    b, s, h, g, bq, bk = _geometry(q, d, dv, block_q, block_k)
    group = _group(q, k, d, dv, g)
    hp, kvb = _heads_a_program(
        _head_plans(h, group, g), lambda hp, kvb: ask(hp, kvb, bq, bk)
    )
    return b, s, h, g, hp, bq, bk, group, kvb


def _group_slot(h0: int, hp: int, kvb: int, group: int, g: int, program):
    """Where the kv head of the query column block at local head ``h0``
    sits in the program's kv block: (column block, slot in it). Both are
    ints where one query program covers the kv block; else the block is
    ONE column block and the slot follows the program's place in its group
    (``program``: its index across the query width) — a traced scalar."""
    if kvb * group == hp:
        return (h0 // group) // g, (h0 // group) % g
    if g == 1:
        return 0, 0
    return 0, ((program * hp + h0) // group) % g


def _swap_halves(x, d: int):
    """[N, 2·d] with its two lane halves exchanged: a lane rotation by d.
    Mosaic rotates 32-bit words only; rows of a narrower dtype are packed
    in pairs into such words lane by lane, so the rotation is the same."""
    if x.dtype.itemsize == 4:
        return pltpu.roll(x, d, 1)
    return pltpu.bitcast(
        pltpu.roll(pltpu.bitcast(x, jnp.uint32), d, 1), x.dtype
    )


def _low_half(shape, d: int):
    return jax.lax.broadcasted_iota(jnp.int32, shape, 1) < d


def _group_tile(ref, at, d: int, g: int):
    """The kv column block ``at`` = (column block, slot) of ``ref``'s tile
    with that slot's kv head in EVERY slot: the tile itself at D=128; at
    D=64 the head's half rotated into the other half too."""
    block, slot = at
    x = ref[:, block * g * d:(block + 1) * g * d]
    if g == 1:
        return x
    other = _swap_halves(x, d)
    return jnp.where(_low_half(x.shape, d) == (slot == 0), x, other)


def _group_tiles(refs, h0, hp, group, g, d, program, made):
    """[each of ``refs``' (k, v) tiles with the kv head of the query column
    block at ``h0`` in every slot], and where that head sits; built once
    per kv head (``made``: the program's tiles so far)."""
    at = _group_slot(h0, hp, refs[0].shape[-1] // d, group, g, program)
    key = h0 // group
    if key not in made:
        made[key] = [_group_tile(ref, at, d, g) for ref in refs]
    return made[key], at


def _fold_group(acc_ref, rows, at, total, d: int, g: int) -> None:
    """Add a query column block's dK or dV ``total`` (head i's part in slot
    i) to its kv head's slot of the accumulator, at the key tile's
    ``rows``."""
    block, slot = at
    lanes = slice(block * g * d, (block + 1) * g * d)
    if g == 1:
        acc_ref[rows, lanes] += total
        return
    both = total + _swap_halves(total, d)  # every slot: the heads' sum
    mine = _low_half(total.shape, d) == (slot == 0)
    acc_ref[rows, lanes] += jnp.where(mine, both, jnp.zeros_like(both))


# ------------------------------------------------------------ causal tiles
#
# With ``causal`` a query sees the keys at its own position and before. A
# (query tile, key tile) pair is then one of three: wholly above the
# diagonal (nothing to do: its K/V blocks are not even fetched — the index
# maps clamp to the last tile that is needed, which Pallas does not copy
# again — and the body is skipped), wholly on or below it (the plain body),
# or crossed by it (the body with an iota mask). Only the crossed tiles pay
# for the mask.
#
# A BAND (a sliding window: ``band`` = w, query i sees keys i-w+1 .. i) has a
# second edge, below the diagonal, and the same three cases on it. A tile
# wholly before the band is not even a grid step: the inner axis of a band
# call is as long as the most tiles any outer tile needs (9 of 32 at
# S=16,384, w=4,096, 512 x 512 tiles) and sweeps from the outer tile's FIRST
# needed tile on. What a query may see is ONE description, ``_Mask``, and
# everything that depends on it reads it: the tile a grid step names
# (``_k_tile``), the sweep's length (``_sweep``), the three cases
# (``_for_tile``), the iota mask (``_tile_mask``), the kernels' names
# and the count of visited tiles (``visited_tiles``).


class _Blocks(NamedTuple):
    """Block diffusion's two streams in one call (see "block-diffusion
    tiles"): [noisy ; clean], ``stream`` positions each, in blocks of
    ``length``."""

    length: int
    stream: int


class _Mask(NamedTuple):
    """What a query may see of the keys, beyond the KV bias."""

    causal: bool = False  # keys at the query's position and before
    band: Optional[int] = None  # and only the last ``band`` of those
    blocks: Optional[_Blocks] = None  # or: the two-stream block rule
    # of the keys before it, those an OPERAND names (see "selected tiles")
    selected: bool = False


def _clip(x, low=None, high=None):
    """``x`` held to [low, high], a Python int or a traced scalar."""
    static = isinstance(x, int)
    if low is not None:
        x = max(x, low) if static else jnp.maximum(x, low)
    if high is not None:
        x = min(x, high) if static else jnp.minimum(x, high)
    return x


def _last_k_tile(qi, bq: int, bk: int):
    """The last key tile query tile ``qi`` needs."""
    return (qi * bq + bq - 1) // bk


def _first_k_tile(mask: _Mask, qi, bq: int, bk: int):
    """The first key tile query tile ``qi`` needs: the one that holds the
    band's first key of the tile's first query; 0 without a band."""
    if mask.band is None:
        return 0
    return _clip(qi * bq - mask.band + 1, low=0) // bk


def _from(first, step):
    """Tile ``step`` of a sweep that starts at ``first``."""
    return step if isinstance(first, int) and first == 0 else first + step


def _k_tile(mask: _Mask, qi, step, bq: int, bk: int):
    """The key tile that step ``step`` of query tile ``qi``'s sweep NAMES
    (an index map's answer): a step past the last needed tile re-names that
    tile, which is not fetched again."""
    if mask.blocks is not None:
        return _bd_tile(mask.blocks, qi, step, bq, bk, clamp=True)[0]
    if not mask.causal:
        return step
    return jnp.minimum(
        _from(_first_k_tile(mask, qi, bq, bk), step), _last_k_tile(qi, bq, bk)
    )


def _sweep(mask: _Mask, nq: int, nk: int, bq: int, bk: int) -> int:
    """Steps of the inner grid axis: the most key tiles a query tile needs.
    Every key tile unless there is a band (or the block rule: the longer of
    its two runs together)."""
    if mask.blocks is not None:
        return max(_bd_tiles_of(mask.blocks, qi, bq, bk) for qi in range(nq))
    if mask.band is None:
        return nk
    return max(
        _last_k_tile(qi, bq, bk) - _first_k_tile(mask, qi, bq, bk) + 1
        for qi in range(nq)
    )


def visited_tiles(seq: int, block_q: int, block_k: int, causal: bool,
                  band: Optional[int] = None,
                  block_diffusion: Optional[int] = None) -> int:
    """(query tile, key tile) pairs a call's kernels compute, crossed ones
    included: 528 of 1,024 at S=16,384 and 512 x 512 tiles under the causal
    mask, 252 under a band of 4,096; 80 of 256 at S=2 x 4,096 under the
    block rule (``seq`` counts both streams)."""
    mask = _mask_of(causal, band, seq, block_diffusion)
    if mask.blocks is not None:
        bq, bk = _bd_blocks(mask.blocks, block_q, block_k)
        return sum(
            _bd_tiles_of(mask.blocks, qi, bq, bk) for qi in range(seq // bq)
        )
    bq, bk = _pick_block(seq, block_q), _pick_block(seq, block_k)
    if not mask.causal:
        return (seq // bq) * (seq // bk)
    return sum(
        _last_k_tile(qi, bq, bk) - _first_k_tile(mask, qi, bq, bk) + 1
        for qi in range(seq // bq)
    )


def _mask_of(causal: bool, band: Optional[int], seq: int,
             block_diffusion: Optional[int] = None,
             selected: bool = False) -> _Mask:
    """The call's mask; a band as long as the sequence IS the causal mask
    (and the causal kernels, under their names)."""
    if selected:
        if band is not None or block_diffusion is not None:
            raise ValueError(
                "selected: the selection is a mask of its own over the "
                "causal triangle (no band, no block rule)"
            )
        return _Mask(causal=True, selected=True)
    if block_diffusion is not None:
        if causal or band is not None or block_diffusion < 1 or seq % (
            2 * block_diffusion
        ):
            raise ValueError(
                f"block_diffusion={block_diffusion}: the block rule is a "
                "mask of its own (no causal, no band) over two streams of "
                f"whole blocks; the call has {seq} positions"
            )
        return _Mask(blocks=_Blocks(int(block_diffusion), seq // 2))
    if band is not None and (not causal or band < 1):
        raise ValueError(
            f"band={band}: a band is a causal mask's second edge "
            "(causal=True, band >= 1)"
        )
    return _Mask(causal, None if band is None or band >= seq else int(band))


def _tile_mask(mask: _Mask, qi, ki, bq: int, bk: int):
    """[Bq, Bk] bool: key position <= query position (and inside the
    band), in tile (qi, ki)."""
    rows = qi * bq + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 0)
    cols = ki * bk + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 1)
    if mask.band is None:
        return cols <= rows
    return (cols <= rows) & (rows - cols < mask.band)


def _for_tile(mask: _Mask, qi, ki, bq: int, bk: int, seq: int, body) -> None:
    """Run ``body(tile mask)`` for tile (qi, ki): None where every key of
    the tile is visible to every query (always, when not causal); not at
    all where none is (a step of the sweep past its last tile)."""
    if not mask.causal:
        body(None)
        return
    q0, k0 = qi * bq, ki * bk
    plain = k0 + bk - 1 <= q0
    some = k0 <= q0 + bq - 1
    if mask.band is not None:  # ... whose sweep may also run past the end
        inside = q0 < seq
        plain &= inside & (q0 + bq - 1 - k0 < mask.band)
        some &= inside & (q0 - (k0 + bk - 1) < mask.band)

    @pl.when(plain)
    def _plain():
        body(None)

    @pl.when(jnp.logical_not(plain) & some)
    def _crossed():
        body(_tile_mask(mask, qi, ki, bq, bk))


def _masked(s, mask):
    return s if mask is None else jnp.where(mask, s, NEG_INF)


# --------------------------------------------------------- selected tiles
#
# A SELECTION (``selected``: learned sparse attention) is a visibility that
# is DATA: query t sees the keys s <= t that an int8 operand ``selection``
# [B, S, S] marks (rows queries; what it marks above the diagonal is never
# read). The sweeps are the causal triangle's — the index maps, the grid and
# the count of visited tiles are the causal call's — and every kernel reads
# the selection's (query tile, key tile) block beside q / k / v, which IS the
# tile's mask: no iota. A tile that holds no selected pair runs no body (no
# MXU work; its blocks are still fetched: the index maps do not read data):
# ``flags`` [B · tiles] int32 in SMEM, one a tile, made from the selection
# by ``selection_tile_flags`` once a call, say which. The backward kernels
# read the same two operands the forward did — they are residuals of the
# custom VJP. A row of a tile with nothing selected scores -1e30 everywhere
# and, while its running max is still the floor, takes weight exp(0) for
# every key of the tile; the first tile that holds one of the row's selected
# keys multiplies that away by exp(-1e30 - m) = 0, and every row has one (a
# selection keeps at least one key a query). Named ``flash_sel_*``.


def selection_tile_flags(selection, block_q: int, block_k: int):
    """[B, S / Bq, S / Bk] int32: 1 where a (query tile, key tile) of
    ``selection`` [B, S, S] holds a selected pair, at the tiles a call with
    these preferred blocks takes."""
    b, s, _ = selection.shape
    bq, bk = _pick_block(s, block_q), _pick_block(s, block_k)
    return jnp.any(
        selection.reshape(b, s // bq, bq, s // bk, bk) != 0, axis=(2, 4)
    ).astype(jnp.int32)


def _selected_kernel(kernel, inputs: int):
    """``kernel`` for a call with a selection: its tile and the tile flags
    ride behind the ``inputs`` other inputs and reach it as ``sel``."""
    def with_selection(*refs, **static):
        return kernel(
            *refs[:inputs], *refs[inputs + 2:],
            sel=refs[inputs:inputs + 2], **static,
        )

    return with_selection


def _for_selected_tile(sel, valid, qi, ki, bk: int, seq: int, body) -> None:
    """Run ``body(the selection's tile)`` for tile (qi, ki) of a step that
    is ``valid`` (inside its sweep), where the tile holds a selected pair."""
    sel_ref, flags_ref = sel
    nk = seq // bk
    tiles = (seq // sel_ref.shape[0]) * nk
    flag = flags_ref[pl.program_id(0) * tiles + qi * nk + ki]

    @pl.when(valid & (flag > 0))
    def _some():
        body(sel_ref[:].astype(jnp.int32) != 0)


def _for_k_step(mask: _Mask, qi, step, bq: int, bk: int, seq: int,
                body, sel=None) -> None:
    """``_for_tile`` for step ``step`` of query tile ``qi``'s key sweep."""
    if mask.selected:
        last = _last_k_tile(qi, bq, bk)
        _for_selected_tile(sel, step <= last, qi, jnp.minimum(step, last),
                           bk, seq, body)
        return
    if mask.blocks is not None:
        _bd_for_step(mask.blocks, qi, step, bq, bk, body)
        return
    _for_tile(mask, qi, _from(_first_k_tile(mask, qi, bq, bk), step), bq, bk,
              seq, body)


# -------------------------------------------------- block-diffusion tiles
#
# Block diffusion trains on TWO copies of a row in one sequence: positions
# 0 .. L-1 hold the NOISY stream, L .. 2L-1 the CLEAN one, both cut into
# blocks of B positions (block of position p of either stream: p // B).
# Query i sees key j iff
#     i clean:  j clean and block(j) <= block(i)
#     i noisy: (j noisy and block(j) == block(i))
#              or (j clean and block(j) < block(i))
# so a noisy query sees keys AFTER it (inside its block), never the clean
# copy of its own block, and no clean query sees a noisy key. Tiles never
# straddle the two streams, nor a block a tile (``_bd_blocks``). In tiles, a
# query tile needs up to TWO runs of key tiles — the clean tiles up to its
# diagonal, and (noisy) its own noisy diagonal tile (``_bd_runs``; forward
# and backward both walk a query tile's keys). A sweep walks the first run,
# then the second (``_bd_tile``); a step past both re-names the last tile and is
# skipped, as a causal sweep's. Within a tile the rule is ONE comparison of
# block indices, low <= block(i) - block(j) <= high, with (low, high) by
# quadrant — clean x clean (0, any), noisy x clean (1, any), noisy x noisy
# (0, 0) — and a tile is plain, crossed or empty by the least and greatest
# difference it holds (``_bd_for_step``): only tiles a stream's diagonal
# crosses pay for the iota mask. At L = 4,096 and 512 x 512 tiles: 80
# visited pairs (36 + 36 + 8) of the 256 a dense call and the 136 a causal
# call over 2L would visit, 24 of them crossed.

_ANY = 2 ** 30  # no upper limit on a difference of block indices


def _where(cond, a, b):
    """``a if cond else b``, for a Python bool or a traced scalar."""
    return (a if cond else b) if isinstance(cond, bool) else jnp.where(
        cond, a, b
    )


def _bd_blocks(blocks: _Blocks, block_q: int, block_k: int):
    """(Bq, Bk) of a block-diffusion call: tiles of ONE stream (they divide
    L, so none straddles the two) that hold whole blocks."""
    bq = _pick_block(blocks.stream, block_q)
    bk = _pick_block(blocks.stream, block_k)
    if bq % blocks.length or bk % blocks.length:
        raise ValueError(
            f"block diffusion: tiles of {bq} x {bk} positions do not hold "
            f"whole blocks of {blocks.length}"
        )
    return bq, bk


def _bd_runs(blocks: _Blocks, qi, bq: int, bk: int):
    """The two runs of key tiles that query tile ``qi`` needs, each (first
    tile, count), in sweep order; a count may be 0. ``qi`` is a Python int
    or a traced scalar."""
    b, nq, nk = blocks.length, blocks.stream // bq, blocks.stream // bk
    clean = qi >= nq
    lo = (qi - _where(clean, nq, 0)) * bq  # in its stream
    hi = lo + bq - 1
    # the CLEAN keys of the blocks up to a clean query's own, before a noisy
    # query's own; then a noisy query's own NOISY blocks
    own = lo // bk
    return (
        (nk, _where(clean, hi, hi - b) // bk + 1),
        (own, _where(clean, 0, hi // bk - own + 1)),
    )


def _bd_tiles_of(blocks: _Blocks, qi, bq: int, bk: int):
    """Key tiles query tile ``qi`` needs: both runs together."""
    return sum(count for _first, count in _bd_runs(blocks, qi, bq, bk))


def _bd_tile(blocks: _Blocks, qi, step, bq: int, bk: int, clamp: bool):
    """(key tile, valid) of step ``step`` of query tile ``qi``'s sweep.
    ``clamp``: an index map's answer, a step past both runs naming the last
    tile again."""
    (a_first, a_count), (b_first, b_count) = _bd_runs(blocks, qi, bq, bk)
    valid = step < a_count + b_count
    if clamp:
        step = jnp.minimum(step, a_count + b_count - 1)
    return jnp.where(
        step < a_count, a_first + step, b_first + step - a_count
    ), valid


def _block_of(x, length: int):
    """Block index of positions ``x`` (non-negative int32)."""
    if length & (length - 1):
        return jax.lax.div(x, jnp.int32(length))
    return jax.lax.shift_right_logical(x, jnp.int32(length.bit_length() - 1))


def _bd_for_step(blocks: _Blocks, qi, step, bq: int, bk: int, body) -> None:
    """Run ``body(tile mask)`` for the tile that step ``step`` of query
    tile ``qi``'s sweep visits: None where the whole tile is visible, not at
    all for a step past the sweep's runs."""
    b, nq, nk = blocks.length, blocks.stream // bq, blocks.stream // bk
    ki, valid = _bd_tile(blocks, qi, step, bq, bk, clamp=False)
    q_clean, k_clean = qi >= nq, ki >= nk
    q0 = (qi - jnp.where(q_clean, nq, 0)) * bq  # in their streams
    k0 = (ki - jnp.where(k_clean, nk, 0)) * bk
    # visible: low <= block(query) - block(key) <= high, by quadrant (a
    # clean query never visits a noisy key tile)
    low = (k_clean & jnp.logical_not(q_clean)).astype(jnp.int32)
    high = jnp.where(k_clean, _ANY, 0)
    least = q0 // b - (k0 + bk - 1) // b  # ... over the tile's pairs
    most = (q0 + bq - 1) // b - k0 // b
    plain = valid & (least >= low) & (most <= high)
    some = valid & (most >= low) & (least <= high)

    @pl.when(plain)
    def _plain():
        body(None)

    @pl.when(jnp.logical_not(plain) & some)
    def _crossed():
        rows = q0 + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 0)
        cols = k0 + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 1)
        apart = _block_of(rows, b) - _block_of(cols, b)
        body((apart >= low) & (apart <= high))


# ------------------------------------------------------------------ forward


def _fwd_kernel(q_ref, k_ref, v_ref, bias_ref, o_ref, lse_ref,
                acc_ref, m_ref, l_ref, *, scale, d, dv, g, mask, seq,
                group=1, sel=None):
    kb = pl.program_id(3)  # a step of the key sweep, not yet a tile
    nk = pl.num_programs(3)
    qi, bq, bk = pl.program_id(2), q_ref.shape[0], k_ref.shape[0]
    program = pl.program_id(1) if group > 1 else None
    blocks = _column_blocks(q_ref.shape[-1], g, d, dv)

    @pl.when(kb == 0)
    def _init():
        acc_ref[:] = jnp.zeros_like(acc_ref)
        m_ref[:] = jnp.full_like(m_ref, NEG_INF)
        l_ref[:] = jnp.zeros_like(l_ref)

    def tile(mask):
        b = bias_ref[:].astype(jnp.float32)  # [1, Bk]
        made = {}
        # several column blocks per program (unrolled): one grid step's DMAs
        # and semaphore work amortise over their heads' matmuls — at D=64
        # the per-head dots are too small to hide the per-program overhead
        # (measured on v5e)
        for h0, cols, vcols in blocks:
            # per head, its window of the block: [Bq, g·D] for both heads
            # at D=64, [Bq, 256] of the 384 lanes at 192
            q = _per_window(d, g, lambda w: q_ref[:, _at(cols, w)])
            if group == 1:
                k = _per_window(d, g, lambda w: k_ref[:, _at(cols, w)])
                v = _per_window(dv, g, lambda w: v_ref[:, _at(vcols, w)])
            else:  # the group's ONE kv head, for every head of the block
                (k_tile, v_tile), _ = _group_tiles(
                    (k_ref, v_ref), h0, q_ref.shape[-1] // d, group, g, d,
                    program, made,
                )
                k, v = [k_tile] * g, [v_tile] * g
            pv, corrs = [None] * len(_segments(dv, g)), []
            for i in range(g):
                h = h0 + i
                s = _masked(
                    _dot(q[i], _only_head(k[i], i, d, g), 1, 1) * scale + b,
                    mask,
                )

                # m and l are TILES [Bq, 128], the row's value in every
                # lane (see "The tiled form's state"): the row max leaves
                # its reduce in every lane too, so nothing below moves a
                # lane; only the lse OUTPUT is a row (HBM tiling).
                m_prev, l_prev = m_ref[h], l_ref[h]
                m_new = jnp.maximum(
                    m_prev, jnp.max(s, axis=-1, keepdims=True)
                )
                p = jnp.exp(s - _across(m_new, s.shape[-1]))
                corr = jnp.exp(m_prev - m_new)
                l_ref[h] = l_prev * corr + jnp.sum(p, axis=-1, keepdims=True)
                m_ref[h] = m_new
                corrs.append(corr)
                _into(pv, i, dv, g, _dot(
                    p.astype(v[i].dtype), _only_head(v[i], i, dv, g), 1, 0
                ))
            for seg, pv_seg in zip(_segments(dv, g), pv):
                at = _at(vcols, seg)
                acc_ref[:, at] = (
                    acc_ref[:, at] * _per_head_lanes(corrs, dv, seg) + pv_seg
                )

    _for_k_step(mask, qi, kb, bq, bk, seq, tile, sel)

    @pl.when(kb == nk - 1)
    def _flush():
        for h0, _cols, vcols in blocks:
            safe_l = [
                jnp.maximum(l_ref[h0 + i], 1e-30) for i in range(g)
            ]  # [Bq, 128] tiles
            for seg in _segments(dv, g):
                at = _at(vcols, seg)
                o_ref[:, at] = (
                    acc_ref[:, at] / _per_head_lanes(safe_l, dv, seg)
                ).astype(o_ref.dtype)
            for i in range(g):
                lse_ref[h0 + i] = _t(  # one lane of the tile -> [1, Bq] row
                    (m_ref[h0 + i] + jnp.log(safe_l[i]))[:, :1]
                )


def _fwd_one_tile_kernel(q_ref, k_ref, v_ref, bias_ref, o_ref, lse_ref, *,
                         scale, d, dv, g, mask):
    """Single-block forward: when one (Bq, Bk) tile covers the whole
    sequence a row's softmax is complete after its one tile, so there is no
    running state to initialise, correct or carry — each head's max, sum and
    p·v are taken once, and a column block is normalised and stored once."""
    s = q_ref.shape[0]
    mask = _tile_mask(mask, 0, 0, s, s) if mask.causal else None
    b = bias_ref[:].astype(jnp.float32)  # [1, S]
    for h0, cols, vcols in _column_blocks(q_ref.shape[-1], g, d, dv):
        q = _per_window(d, g, lambda w: q_ref[:, _at(cols, w)])
        k = _per_window(d, g, lambda w: k_ref[:, _at(cols, w)])
        v = _per_window(dv, g, lambda w: v_ref[:, _at(vcols, w)])
        pv, ls = [None] * len(_segments(dv, g)), []
        for i in range(g):
            x = _masked(
                _dot(q[i], _only_head(k[i], i, d, g), 1, 1) * scale + b, mask
            )
            # the floor the tiled kernel's state starts from: a row whose
            # every score is -inf stays finite
            m = jnp.maximum(jnp.max(x, axis=-1, keepdims=True), NEG_INF)
            p = jnp.exp(x - m)
            l = jnp.maximum(jnp.sum(p, axis=-1, keepdims=True), 1e-30)
            lse_ref[h0 + i] = _t(m + jnp.log(l))  # [S, 1] -> [1, S] row
            ls.append(l)
            _into(pv, i, dv, g, _dot(
                p.astype(v[i].dtype), _only_head(v[i], i, dv, g), 1, 0
            ))
        for seg, pv_seg in zip(_segments(dv, g), pv):
            o_ref[:, _at(vcols, seg)] = (
                pv_seg / _per_head_lanes(ls, dv, seg)
            ).astype(o_ref.dtype)


def _name(kernel: str, mask: _Mask, d: int, dv: int,
          group: int = 1) -> str:
    """The causal kernels keep names of their own in a device trace, and so
    do the two-width ones (latent attention: q/k wider than v and out), the
    grouped-query ones (fewer kv heads than heads) and every call with a
    band (its metadata says how long, and its head counts) or the block
    rule (its blocks' and its streams' length)."""
    if mask.selected:
        return f"flash_sel_{kernel}"
    if mask.blocks is not None:
        return f"flash_bd_{kernel}"
    if mask.band is not None:
        return f"flash_band_{kernel}"
    causal = mask.causal
    if group > 1:
        return f"flash_gqa_{kernel}" if causal else f"flash_gqa_full_{kernel}"
    if d != dv:
        return f"flash_mla_{kernel}" if causal else f"flash_mla_full_{kernel}"
    return f"flash_causal_{kernel}" if causal else f"flash_{kernel}"


def _fwd(q, k, v, bias, d, dv, block_q, block_k, mask, interpret,
         sel=None):
    """Returns (out [B, S, H·dv], lse [B·H, 1, S]). ``sel``: a selected
    call's (selection, tile flags)."""
    # grouped-query calls take the tiled form at every length (one tile is
    # then a grid of one): the one-tile kernels have no kv block of their
    # own — nor a selection's tile
    if (_one_tile(q.shape[1], block_q, block_k) and k.shape == q.shape
            and sel is None):
        return _fwd_one_tile(q, k, v, bias, d, dv, mask, interpret)
    return _fwd_tiled(q, k, v, bias, d, dv, block_q, block_k, mask,
                      interpret, sel)


def _selection_specs(bq: int, bk: int, at):
    """The in_specs of a selected call's two extra operands: the selection's
    (query tile, key tile) block, at ``at(*grid indices)`` = (batch, query
    tile, key tile), and the tile flags whole in SMEM."""
    return [
        pl.BlockSpec((None, bq, bk), at),
        pl.BlockSpec(memory_space=pltpu.SMEM),
    ]


def _fwd_ask(hp: int, kvb: int, bq: int, bk: int, *, d: int, dv: int,
             size: int, selected: bool = False) -> int:
    """Bytes of scoped VMEM a tiled forward call of ``hp`` heads a program
    asks for (nothing in it grows with the sequence). With the state lane-dense nothing orders a program's heads, so the
    scheduler starts every head's q·kᵀ ahead of the first head's softmax and
    holds each head's score tile (float32, then bf16 probabilities:
    6·Bq·Bk bytes) on its stack at once, beside the blocks (twice: the
    pipeline's two buffers) and the state."""
    blocks = 2 * size * (bq * hp * (d + dv) + bk * kvb * (d + dv))
    state = 4 * bq * hp * (dv + 2 * STATE_LANES)
    # a selection's int8 tile, twice (the pipeline's two buffers), and the
    # tile's mask widened for the select
    need = blocks + state + hp * 6 * bq * bk + (6 * bq * bk if selected else 0)
    return need + 4 * 2**20


def _fwd_vmem(q, bq: int, bk: int, hp: int, kvb: int, d: int, dv: int,
              selected: bool = False):
    """Compiler parameters of a tiled forward call: None — the compiler's
    own scoped-VMEM limit, 16 MiB on a v5e — unless the call needs more
    (``_fwd_ask``): two heads of 128 at 512 x 512 fit it, a group of seven
    or eight (26-28 MiB) asks for what it needs."""
    ask = _fwd_ask(hp, kvb, bq, bk, d=d, dv=dv, size=q.dtype.itemsize,
                   selected=selected)
    if ask <= 18 * 2**20:
        return None
    return pltpu.CompilerParams(vmem_limit_bytes=ask)


def _fwd_geometry(q, k, d: int, dv: int, block_q: int, block_k: int,
                  selected: bool = False):
    """``_tiled_geometry`` of a tiled forward call: it holds nothing for the
    sequence, so at the cells' tiles the most heads the call may take."""
    return _tiled_geometry(q, k, d, dv, block_q, block_k, functools.partial(
        _fwd_ask, d=d, dv=dv, size=q.dtype.itemsize, selected=selected,
    ))


def _fwd_tiled(q, k, v, bias, d, dv, block_q, block_k, mask, interpret,
               sel=None):
    b, s, h, g, hp, bq, bk, group, kvb = _fwd_geometry(
        q, k, d, dv, block_q, block_k, sel is not None
    )
    hpb = h // hp  # programs across the width

    def k_at(j, kb):  # a tile above the diagonal re-names the last needed
        return _k_tile(mask, j, kb, bq, bk)

    def kv_at(p):  # the kv block of query program p
        return p if group == 1 else p * hp // group // kvb

    kernel = functools.partial(
        _fwd_kernel, scale=1.0 / (d ** 0.5), d=d, dv=dv, g=g,
        mask=mask, seq=s, group=group,
    )
    selection = [] if sel is None else _selection_specs(
        bq, bk, lambda n, p, j, kb: (n, j, k_at(j, kb))
    )
    out, lse = pl.pallas_call(
        kernel if sel is None else _selected_kernel(kernel, 4),
        grid=(b, hpb, s // bq, _sweep(mask, s // bq, s // bk, bq, bk)),
        in_specs=[
            pl.BlockSpec((None, bq, hp * d), lambda n, p, j, kb: (n, j, p)),
            pl.BlockSpec((None, bk, kvb * d),
                         lambda n, p, j, kb: (n, k_at(j, kb), kv_at(p))),
            pl.BlockSpec((None, bk, kvb * dv),
                         lambda n, p, j, kb: (n, k_at(j, kb), kv_at(p))),
            pl.BlockSpec((None, 1, bk),
                         lambda n, p, j, kb: (n, 0, k_at(j, kb))),
            *selection,
        ],
        out_specs=[
            pl.BlockSpec((None, bq, hp * dv), lambda n, p, j, kb: (n, j, p)),
            pl.BlockSpec((hp, 1, bq),
                         lambda n, p, j, kb: (n * hpb + p, 0, j)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((b, s, h * dv), q.dtype),
            jax.ShapeDtypeStruct((b * h, 1, s), jnp.float32),
        ],
        scratch_shapes=[
            pltpu.VMEM((bq, hp * dv), jnp.float32),
            pltpu.VMEM((hp, bq, STATE_LANES), jnp.float32),
            pltpu.VMEM((hp, bq, STATE_LANES), jnp.float32),
        ],
        interpret=interpret,
        name=_name("fwd", mask, d, dv, group),
        metadata=_metadata(d, dv, g, q, k, mask, hp),
        compiler_params=_fwd_vmem(q, bq, bk, hp, kvb, d, dv,
                                  sel is not None),
    )(q, k, v, bias, *(sel or ()))
    return out, lse


def _fwd_one_tile(q, k, v, bias, d, dv, mask, interpret):
    b, s, h, g, _bq, _bk = _geometry(q, d, dv, q.shape[1], q.shape[1])
    hp = _pick_heads(h, g, s, s, budget_mb=6.0)
    hpb = h // hp
    wide = pl.BlockSpec((None, s, hp * d), lambda n, p: (n, 0, p))
    wide_v = pl.BlockSpec((None, s, hp * dv), lambda n, p: (n, 0, p))
    out, lse = pl.pallas_call(
        functools.partial(
            _fwd_one_tile_kernel, scale=1.0 / (d ** 0.5), d=d, dv=dv, g=g,
            mask=mask,
        ),
        grid=(b, hpb),
        in_specs=[
            wide, wide, wide_v,
            pl.BlockSpec((None, 1, s), lambda n, p: (n, 0, 0)),
        ],
        out_specs=[
            wide_v,
            pl.BlockSpec((hp, 1, s), lambda n, p: (n * hpb + p, 0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct(v.shape, q.dtype),
            jax.ShapeDtypeStruct((b * h, 1, s), jnp.float32),
        ],
        interpret=interpret,
        name=_name("fwd", mask, d, dv),
        # the same name in a device trace; a lowered module tells the two
        # forward forms apart by this (tools/tpu_aot.py counts them)
        metadata={"form": "one_tile", **(_metadata(d, dv, g, q, k, mask)
                                         or {})},
    )(q, k, v, bias)
    return out, lse


# ----------------------------------------------------------------- backward

_Head = collections.namedtuple("_Head", "p ds q k do")


def _backward_heads(refs, bias_ref, lse_ref, h0, cols, vcols, mask, *,
                    scale, d, dv, g, group_kv=None):
    """The g heads of one column block (heads ``h0``.., lanes ``cols`` of
    the program's tiles), one at a time: each head's probability tile ``p``
    and the gradient ``ds`` of its scores ([Bq, Bk], recomputed from the
    residuals in fp32, cast for the MXU), with q, k and dO cut down to that
    head's lanes of its window for the products that follow (``_into``
    adds them up). ``mask``: the causal mask of a tile the diagonal
    crosses, else None. ``group_kv``: a grouped-query block's (k, v) tiles
    (``_group_tiles``) in place of the refs' own columns."""
    # dO stays in its native (bf16) dtype for the dots — MXU at full rate
    q_ref, k_ref, v_ref, do_ref, o_ref = refs
    q = _per_window(d, g, lambda w: q_ref[:, _at(cols, w)])
    if group_kv is None:
        k = _per_window(d, g, lambda w: k_ref[:, _at(cols, w)])
    else:
        k = [group_kv[0]] * g

    def v_side(w):  # v, dO and dO ⊙ O over one window
        at = _at(vcols, w)
        v = v_ref[:, at] if group_kv is None else group_kv[1]
        do, o = do_ref[:, at], o_ref[:, at]
        return v, do, do.astype(jnp.float32) * o.astype(jnp.float32)

    v_do_prod = _per_window(dv, g, v_side)
    b = bias_ref[:].astype(jnp.float32)  # [1, Bk]
    for i, (v, do, prod) in enumerate(v_do_prod):
        k_i = _only_head(k[i], i, d, g)
        s = _masked(_dot(q[i], k_i, 1, 1) * scale + b, mask)
        p = jnp.exp(s - _t(lse_ref[h0 + i]))  # [1, Bq] row -> column
        dp = _dot(do, _only_head(v, i, dv, g), 1, 1)
        # delta = rowsum(dO ⊙ O) per head, as the COLUMN the math needs
        delta = jnp.sum(_only_head(prod, i, dv, g), axis=-1, keepdims=True)
        ds = p * (dp - delta) * scale
        yield _Head(p.astype(do.dtype), ds.astype(q[i].dtype),
                    _only_head(q[i], i, d, g), k_i,
                    _only_head(do, i, dv, g))


def _each_key_tile(ref, bk: int, body) -> None:
    """``body(rows)`` for each run of ``bk`` rows of a whole-sequence
    ``ref``, as a loop (a sequence of tiles is not unrolled)."""
    def step(t, carry):
        body(pl.ds(pl.multiple_of(t * bk, bk), bk))
        return carry

    jax.lax.fori_loop(0, ref.shape[0] // bk, step, 0)


def _bwd_tiled_kernel(q_ref, k_ref, v_ref, bias_ref, lse_ref, do_ref, o_ref,
                      dq_ref, dk_ref, dv_ref, dq_acc_ref, dk_acc_ref,
                      dv_acc_ref, *, scale, d, dv, g, mask, seq, group=1,
                      sel=None):
    """The tiled backward in ONE sweep (see "The tiled backward"): grid (B,
    kv blocks, the kv block's query programs — a grouped call's ``members``
    —, query tile, a step of the key sweep). dq's accumulator lives over a
    key sweep; dk's and dv's hold the kv block's WHOLE sequence and live
    over everything inside the kv block's axis."""
    member, members = pl.program_id(2), pl.num_programs(2)
    qi, nq = pl.program_id(3), pl.num_programs(3)
    kb, nk = pl.program_id(4), pl.num_programs(4)  # a step, not yet a tile
    bq, bk = q_ref.shape[0], k_ref.shape[0]
    program = pl.program_id(1) * members + member
    # the rows of dk / dv this step's key tile owns
    rows = pl.ds(pl.multiple_of(_k_tile(mask, qi, kb, bq, bk) * bk, bk), bk)

    @pl.when(kb == 0)
    def _init():
        dq_acc_ref[:] = jnp.zeros_like(dq_acc_ref)

    @pl.when((member == 0) & (qi == 0) & (kb == 0))
    def _init_kv():
        def zero(tile):
            dk_acc_ref[tile, :] = jnp.zeros((bk, dk_acc_ref.shape[-1]),
                                            jnp.float32)
            dv_acc_ref[tile, :] = jnp.zeros((bk, dv_acc_ref.shape[-1]),
                                            jnp.float32)

        _each_key_tile(dk_acc_ref, bk, zero)

    def tile(mask):
        made = {}
        for h0, cols, vcols in _column_blocks(q_ref.shape[-1], g, d, dv):
            dq_at = _segments_at(cols, d, g)
            dq = [dq_acc_ref[:, at] for at in dq_at]
            if group == 1:
                group_kv = None
                dk_at, dv_at = dq_at, _segments_at(vcols, dv, g)
                dk = [dk_acc_ref[rows, at] for at in dk_at]
                dv_ = [dv_acc_ref[rows, at] for at in dv_at]
            else:  # sum the block's heads, fold onto their kv head
                group_kv, slot = _group_tiles(
                    (k_ref, v_ref), h0, q_ref.shape[-1] // d, group, g, d,
                    program, made,
                )
                dk, dv_ = [None], [None]
            # ONE score / probability tile a head feeds the three products
            for i, head in enumerate(_backward_heads(
                (q_ref, k_ref, v_ref, do_ref, o_ref), bias_ref, lse_ref, h0,
                cols, vcols, mask, scale=scale, d=d, dv=dv, g=g,
                group_kv=group_kv,
            )):
                _into(dv_, i, dv, g, _dot(head.p, head.do, 0, 0))
                _into(dk, i, d, g, _dot(head.ds, head.q, 0, 0))
                _into(dq, i, d, g, _dot(head.ds, head.k, 1, 0))
            for at, total in zip(dq_at, dq):
                dq_acc_ref[:, at] = total
            if group > 1:
                _fold_group(dk_acc_ref, rows, slot, dk[0], d, g)
                _fold_group(dv_acc_ref, rows, slot, dv_[0], d, g)
            else:
                for at, total in zip(dk_at, dk):
                    dk_acc_ref[rows, at] = total
                for at, total in zip(dv_at, dv_):
                    dv_acc_ref[rows, at] = total

    _for_k_step(mask, qi, kb, bq, bk, seq, tile, sel)

    @pl.when(kb == nk - 1)
    def _flush():
        dq_ref[:] = dq_acc_ref[:].astype(dq_ref.dtype)

    @pl.when((member == members - 1) & (qi == nq - 1) & (kb == nk - 1))
    def _flush_kv():
        def cast(tile):
            dk_ref[tile, :] = dk_acc_ref[tile, :].astype(dk_ref.dtype)
            dv_ref[tile, :] = dv_acc_ref[tile, :].astype(dv_ref.dtype)

        _each_key_tile(dk_acc_ref, bk, cast)


def _dqkv_fused_kernel(q_ref, k_ref, v_ref, bias_ref, lse_ref, do_ref,
                       o_ref, dq_ref, dk_ref, dv_ref, *, scale, d, dv, g,
                       mask):
    """Single-block backward: when one (Bq, Bk) tile covers the whole
    sequence, dq/dk/dv share ONE score/prob computation and one set of
    input DMAs instead of recomputing them in two kernels."""
    s = q_ref.shape[0]
    mask = _tile_mask(mask, 0, 0, s, s) if mask.causal else None
    for h0, cols, vcols in _column_blocks(q_ref.shape[-1], g, d, dv):
        dq, dk = ([None] * len(_segments(d, g)) for _ in range(2))
        dv_ = [None] * len(_segments(dv, g))
        for i, head in enumerate(_backward_heads(
            (q_ref, k_ref, v_ref, do_ref, o_ref), bias_ref, lse_ref, h0,
            cols, vcols, mask, scale=scale, d=d, dv=dv, g=g,
        )):
            _into(dv_, i, dv, g, _dot(head.p, head.do, 0, 0))
            _into(dq, i, d, g, _dot(head.ds, head.k, 1, 0))
            _into(dk, i, d, g, _dot(head.ds, head.q, 0, 0))
        for ref, block, width, totals in (
            (dq_ref, cols, d, dq), (dk_ref, cols, d, dk),
            (dv_ref, vcols, dv, dv_),
        ):
            for at, total in zip(_segments_at(block, width, g), totals):
                ref[:, at] = total.astype(ref.dtype)


def _bwd_resident(s: int, kvb: int, d: int, dv: int, size: int):
    """Bytes a tiled backward call keeps in VMEM for a kv block's WHOLE
    sequence: (the float32 dk and dv accumulators, the dk and dv output
    blocks in their own dtype, twice — the pipeline's two buffers)."""
    return 4 * s * kvb * (d + dv), 2 * size * s * kvb * (d + dv)


def _bwd_ask(s: int, hp: int, kvb: int, bq: int, bk: int, *, d: int,
             dv: int, size: int, selected: bool = False) -> int:
    """Bytes of scoped VMEM the tiled backward of ``hp`` heads a program
    asks for: what is resident for the whole sequence (``_bwd_resident``),
    the blocks of a step (q, dO, O and dq a query tile; k and v a key tile;
    twice), dq's accumulator, and a program's heads' transients (s, p, dp
    and ds of a head, 18·Bq·Bk bytes: three times the forward's figure)."""
    blocks = 2 * size * (2 * bq * hp * (d + dv) + bk * kvb * (d + dv))
    transients = hp * 18 * bq * bk + (6 * bq * bk if selected else 0)
    return (sum(_bwd_resident(s, kvb, d, dv, size)) + blocks
            + 4 * bq * hp * d + transients + 4 * 2**20)


def _bwd_geometry(q, k, d: int, dv: int, block_q: int, block_k: int,
                  selected: bool = False):
    """``_tiled_geometry`` of a tiled backward call: the most heads a
    program whose transients fit beside what the kv block holds for the
    whole sequence — a long sequence takes fewer heads (and, with as many
    kv heads as heads, a narrower kv block) before it is refused."""
    return _tiled_geometry(q, k, d, dv, block_q, block_k, functools.partial(
        _bwd_ask, q.shape[1], d=d, dv=dv, size=q.dtype.itemsize,
        selected=selected,
    ))


def _bwd_vmem(q, k, d: int, dv: int, block_q: int, block_k: int,
              selected: bool = False):
    """Compiler parameters of the tiled backward call on [B, S, H·d] ``q``
    and [B, S, H_kv·d] ``k`` (arrays or their shapes): the scoped VMEM it
    asks for (``_bwd_ask`` at the heads ``_bwd_geometry`` chose). None —
    the compiler's own limit, 16 MiB on a v5e — where that fits it (the
    test models). The sequence is bounded HERE: a call whose ask passes
    ``_VMEM_CEILING`` at ONE column block a program is refused (4 + 4 bytes
    a row and lane of the kv block: S = 32,768 still fits at a kv block of
    one head of 128, four heads a program where 16,384 takes the group's
    eight, and 49,152 at one; 53,248 does not; every cell is at or under
    16,384)."""
    _b, s, _h, _g, hp, bq, bk, _group, kvb = _bwd_geometry(
        q, k, d, dv, block_q, block_k, selected
    )
    size = q.dtype.itemsize
    ask = _bwd_ask(s, hp, kvb, bq, bk, d=d, dv=dv, size=size,
                   selected=selected)
    if ask <= 18 * 2**20:
        return None
    if ask > _VMEM_CEILING:
        raise ValueError(
            f"tiled flash backward at S={s}: dk and dv of a kv block "
            f"({kvb} heads of {d} / {dv}) are held for the whole sequence "
            f"in VMEM, {sum(_bwd_resident(s, kvb, d, dv, size)) / 2**20:.0f}"
            f" MiB of the {ask / 2**20:.0f} the call asks for at {hp} heads "
            f"a program, past the {_VMEM_CEILING // 2**20} MiB a core can "
            "be asked for; run the sequence in shorter rows"
        )
    return pltpu.CompilerParams(vmem_limit_bytes=ask)


def _bwd(q, k, v, bias, lse, do, out, d, dv, block_q, block_k, mask,
         interpret, sel=None):
    # grouped-query calls take the tiled form at every length, as a
    # selection's (see ``_fwd``)
    if (_one_tile(q.shape[1], block_q, block_k) and k.shape == q.shape
            and sel is None):
        return _bwd_fused(q, k, v, bias, lse, do, out, d, dv, mask,
                          interpret)
    return _bwd_tiled(q, k, v, bias, lse, do, out, d, dv, block_q, block_k,
                      mask, interpret, sel)


def _bwd_tiled(q, k, v, bias, lse, do, out, d, dv, block_q, block_k, mask,
               interpret, sel=None):
    """dq, dk and dv of a tiled call from ONE kernel on the forward's walk
    (query tile outer, key sweep inner), with one more axis outside the
    query tile's: the query programs that share a kv block (``members``;
    one where k has as many heads as q)."""
    b, s, h, g, hp, bq, bk, group, kvb = _bwd_geometry(
        q, k, d, dv, block_q, block_k, sel is not None
    )
    hpb, members, nq, nk = h // hp, kvb * group // hp, s // bq, s // bk

    def k_at(j, kb):  # a step past the sweep re-names the last needed tile
        return _k_tile(mask, j, kb, bq, bk)

    # grid (B, kv block, member, query tile, key step): each spec follows
    # the query tile or the key tile, at one of the two widths (q, k and
    # their gradients; v, out, dO and dv)
    def q_side(width):
        return pl.BlockSpec(
            (None, bq, hp * width),
            lambda n, c, m, j, kb: (n, j, c * members + m),
        )

    def kv_side(width):
        return pl.BlockSpec((None, bk, kvb * width),
                            lambda n, c, m, j, kb: (n, k_at(j, kb), c))

    def kv_whole(width):  # resident over the kv block's whole walk
        return pl.BlockSpec((None, s, kvb * width),
                            lambda n, c, m, j, kb: (n, 0, c))

    kernel = functools.partial(
        _bwd_tiled_kernel, scale=1.0 / (d ** 0.5), d=d, dv=dv, g=g,
        mask=mask, seq=s, group=group,
    )
    selection = [] if sel is None else _selection_specs(
        bq, bk, lambda n, c, m, j, kb: (n, j, k_at(j, kb))
    )
    return pl.pallas_call(
        kernel if sel is None else _selected_kernel(kernel, 7),
        grid=(b, hpb // members, members, nq, _sweep(mask, nq, nk, bq, bk)),
        in_specs=[  # q, k, v, bias, lse, dO, O(, selection, flags)
            q_side(d), kv_side(d), kv_side(dv),
            pl.BlockSpec((None, 1, bk),
                         lambda n, c, m, j, kb: (n, 0, k_at(j, kb))),
            pl.BlockSpec(
                (hp, 1, bq),
                lambda n, c, m, j, kb: (n * hpb + c * members + m, 0, j),
            ),
            q_side(dv), q_side(dv),
            *selection,
        ],
        out_specs=[q_side(d), kv_whole(d), kv_whole(dv)],
        out_shape=[
            jax.ShapeDtypeStruct(q.shape, q.dtype),
            jax.ShapeDtypeStruct(k.shape, k.dtype),
            jax.ShapeDtypeStruct(v.shape, v.dtype),
        ],
        scratch_shapes=[
            pltpu.VMEM((bq, hp * d), jnp.float32),
            pltpu.VMEM((s, kvb * d), jnp.float32),
            pltpu.VMEM((s, kvb * dv), jnp.float32),
        ],
        interpret=interpret,
        name=_name("bwd_tiled", mask, d, dv, group),
        metadata=_metadata(d, dv, g, q, k, mask, hp),
        compiler_params=_bwd_vmem(q, k, d, dv, block_q, block_k,
                                  sel is not None),
    )(q, k, v, bias, lse, do, out, *(sel or ()))


def _bwd_fused(q, k, v, bias, lse, do, out, d, dv, mask, interpret):
    b, s, h, g, _bq, _bk = _geometry(q, d, dv, q.shape[1], q.shape[1])
    # fused kernel holds s, p, dp, ds (~4 full tiles) at once per head
    hp = _pick_heads(h, g, s, s, budget_mb=3.0)
    hpb = h // hp
    wide = pl.BlockSpec((None, s, hp * d), lambda n, p: (n, 0, p))
    wide_v = pl.BlockSpec((None, s, hp * dv), lambda n, p: (n, 0, p))
    dq, dk, dv_ = pl.pallas_call(
        functools.partial(
            _dqkv_fused_kernel, scale=1.0 / (d ** 0.5), d=d, dv=dv, g=g,
            mask=mask,
        ),
        grid=(b, hpb),
        in_specs=[
            wide, wide, wide_v,
            pl.BlockSpec((None, 1, s), lambda n, p: (n, 0, 0)),
            pl.BlockSpec((hp, 1, s), lambda n, p: (n * hpb + p, 0, 0)),
            wide_v, wide_v,
        ],
        out_specs=[wide, wide, wide_v],
        out_shape=[
            jax.ShapeDtypeStruct(q.shape, q.dtype),
            jax.ShapeDtypeStruct(k.shape, k.dtype),
            jax.ShapeDtypeStruct(v.shape, v.dtype),
        ],
        interpret=interpret,
        name=_name("bwd_fused", mask, d, dv),
        metadata=_metadata(d, dv, g, q, k, mask),
    )(q, k, v, bias, lse, do, out)
    return dq, dk, dv_


# --------------------------------------------------------------- public op


@functools.partial(jax.custom_vjp, nondiff_argnums=(4, 5, 6, 7, 8, 9))
def _flash(q, k, v, bias, d, dv, block_q, block_k, mask, interpret):
    out, _lse = _fwd(q, k, v, bias, d, dv, block_q, block_k, mask,
                     interpret)
    return out


def _flash_fwd(q, k, v, bias, d, dv, block_q, block_k, mask, interpret):
    # ``out`` is what the remat policies save per layer (a Pallas output),
    # in the layout the out-projection reads: no lane padding at any D
    out, lse = _fwd(q, k, v, bias, d, dv, block_q, block_k, mask,
                    interpret)
    return out, (q, k, v, bias, out, lse)


def _flash_bwd(d, dv, block_q, block_k, mask, interpret, residuals, g):
    q, k, v, bias, out, lse = residuals
    dq, dk, dv_ = _bwd(q, k, v, bias, lse, g, out, d, dv, block_q, block_k,
                       mask, interpret)
    # the mask bias is non-differentiable input
    return dq, dk, dv_, jnp.zeros_like(bias)


_flash.defvjp(_flash_fwd, _flash_bwd)


@functools.partial(jax.custom_vjp, nondiff_argnums=(6, 7, 8, 9, 10, 11))
def _flash_selected(q, k, v, bias, selection, flags, d, dv, block_q, block_k,
                    mask, interpret):
    """The selected call: (out, lse [B·H, 1, S]). The log-sum-exp is an
    OUTPUT here — a caller's second loss reads the probabilities the
    kernels normalised by — with no gradient of its own: whoever reads it
    reads a detached value, and the backward drops its cotangent."""
    return _fwd(q, k, v, bias, d, dv, block_q, block_k, mask, interpret,
                (selection, flags))


def _flash_selected_fwd(q, k, v, bias, selection, flags, d, dv, block_q,
                        block_k, mask, interpret):
    out, lse = _fwd(q, k, v, bias, d, dv, block_q, block_k, mask, interpret,
                    (selection, flags))
    # the backward's selection IS the forward's: a residual, not a replay
    return (out, lse), (q, k, v, bias, selection, flags, out, lse)


def _flash_selected_bwd(d, dv, block_q, block_k, mask, interpret, residuals,
                        g):
    q, k, v, bias, selection, flags, out, lse = residuals
    dq, dk, dv_ = _bwd(q, k, v, bias, lse, g[0], out, d, dv, block_q,
                       block_k, mask, interpret, (selection, flags))
    return dq, dk, dv_, jnp.zeros_like(bias), *(
        np.zeros(x.shape, jax.dtypes.float0) for x in (selection, flags)
    )


_flash_selected.defvjp(_flash_selected_fwd, _flash_selected_bwd)


def _flash_local(q, k, v, bias, d, dv, block_q, block_k, mask, interpret):
    """The op on [B, S, H·D] operands as ONE device sees them (the whole
    arrays off-mesh, this device's batch/head shard under shard_map)."""
    # named as the dense layers give them, which is what the kernels read:
    # the fused_ln remat policy saves exactly what the flash backward
    # consumes, in the one layout the stash and both kernels share
    q, k, v = (checkpoint_name(x, "flash_qkv") for x in (q, k, v))
    bias = bias[:, None, :].astype(jnp.float32)  # [B, 1, S] row per sample
    return _flash(q, k, v, bias, d, dv, block_q, block_k, mask, interpret)


def flash_attention(
    q: jnp.ndarray,  # [B, S, H, D]
    k: jnp.ndarray,  # [B, S, H_kv, D]: H_kv = H, or a divisor (grouped)
    v: jnp.ndarray,  # [B, S, H_kv, Dv]: Dv = D, or narrower (latent)
    bias: Optional[jnp.ndarray] = None,  # [B, S_kv] additive
    block_q: int = 512,
    block_k: int = 512,
    interpret: Optional[bool] = None,
    mesh: Optional[Mesh] = None,
    causal: bool = False,
    band: Optional[int] = None,
    block_diffusion: Optional[int] = None,
    selection: Optional[jnp.ndarray] = None,  # [B, S, S] int8, rows queries
) -> jnp.ndarray:
    """Exact fused attention; drop-in for dense/blockwise attention.

    ``interpret=None`` takes ``pallas_interpret()``: compiled on TPU,
    interpreter elsewhere (so CPU tests and the virtual mesh exercise
    identical kernel code). On TPU, effective block sizes must be multiples
    of 128 (or the whole sequence) for the bias/lse BlockSpecs to be
    Mosaic-legal. ``mesh``: the device mesh the caller's jit spans — the op
    then runs per shard under ``shard_map`` (see module docstring).
    ``causal``: a decoder's mask, inside the kernels (query i sees keys
    0..i; the KV bias still applies on top). ``band``: with ``causal``, a
    sliding window — query i sees keys i-band+1 .. i, its own position
    counted; tiles outside the band are neither grid steps nor fetched, the
    kernels are named ``flash_band_*``, and a band no shorter than the
    sequence IS the causal call (the same kernels, names and bits).
    ``block_diffusion``: a mask of its own — the S positions are a noisy
    stream then a clean one, S / 2 each, in blocks of this many positions,
    under the rule of "block-diffusion tiles" (``flash_bd_*``); the tiles
    are tiles of one stream. ``v``
    may have a head width of its own (latent attention: q and k 192 wide, v and the result 128): the
    same kernels with two column-block widths, scores scaled by
    1/sqrt(q's width), named ``flash_mla_*`` in a device trace; nothing is
    padded. ``k`` and ``v`` may have FEWER heads than ``q`` (grouped-query
    attention: each kv head serves H / H_kv adjacent query heads): they are
    read at their own width, dk / dv are summed over a group inside the
    kernel, named ``flash_gqa_*``; head widths 64 and 128. A group of
    eight or fewer gets a whole group a program — its kv head fetched
    once, dk / dv summed inside the program — where the VMEM the call asks
    for holds it (module docstring, "Heads a program"); an odd group (28
    heads over 4: seven) is taken at head width 128 alone (at 64 two heads
    share a lane tile and a kv head: the group must be even).
    ``selection``: a mask of its own
    that is an operand — query t sees the keys s <= t where this
    [B, S, S] (int8, rows queries) is not 0; see "selected tiles". The call
    then returns (out, lse [B, H, S] float32): the log-sum-exp over the
    selected keys is handed out DETACHED (for a loss on the probabilities,
    computed outside), and the backward kernels read the selection the
    forward read (a residual; under a layer remat policy it is kept where
    the caller named it ``attn_selection``, else replayed). One device: no
    ``mesh``.
    """
    if interpret is None:
        interpret = pallas_interpret()
    b, s, h, d = q.shape
    kvh, dv = v.shape[-2:]
    if bias is None:
        bias = jnp.zeros((b, s), jnp.float32)
    mask = _mask_of(causal, band, s, block_diffusion, selection is not None)
    if mask.selected:
        if mesh is not None:
            raise ValueError(
                "selected: a call with a ``selection`` runs on one device "
                "(no mesh)"
            )
        # the caller names the selection where it MAKES it ("attn_selection",
        # models/remat.py: kept with the kernels' operands): every reader of
        # it then reads the one kept array — named here it would be kept a
        # second time for whoever else reads the caller's own value
        flags = selection_tile_flags(selection, block_q, block_k).reshape(-1)
        q, k, v = (
            checkpoint_name(x.reshape(b, s, -1), "flash_qkv")
            for x in (q, k, v)
        )
        out, lse = _flash_selected(
            q, k, v, bias[:, None, :].astype(jnp.float32), selection, flags,
            d, dv, block_q, block_k, mask, interpret,
        )
        return out.reshape(b, s, h, dv), lse.reshape(b, h, s)
    if mask.blocks is not None:
        block_q, block_k = _bd_blocks(mask.blocks, block_q, block_k)
    op = functools.partial(
        _flash_local, d=d, dv=dv, block_q=block_q, block_k=block_k,
        mask=mask, interpret=interpret,
    )
    if mesh is not None:
        # heads over "model": a shard's columns are its (H/tp)·D
        qkv = P(mesh_axis(mesh, "data"), None, mesh_axis(mesh, "model"))
        # check_vma=False: pallas_call outputs carry no varying-axes type
        op = jax.shard_map(
            op, mesh=mesh,
            in_specs=(qkv, qkv, qkv, P(mesh_axis(mesh, "data"), None)),
            out_specs=qkv, check_vma=False,
        )
    # [B, S, H, D] <-> [B, S, H·D] is the same bytes: the dense layers'
    # own layout goes in and comes out, nothing is transposed
    return op(
        q.reshape(b, s, h * d), k.reshape(b, s, kvh * d),
        v.reshape(b, s, kvh * dv), bias,
    ).reshape(b, s, h, dv)
