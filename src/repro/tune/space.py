"""Search spaces for the kernel autotuner.

The paper's §5 design-space sweep picks a different systolic-array tiling per
device and precision; this module is the software analogue: the set of LEGAL
block-shape candidates per kernel, deterministically ordered so a tuning run
is reproducible and a budget-limited run always tries the same prefix.

Legality encodes each kernel's real constraints:
  * GEMM (baseline/fip/ffip): power-of-2 blocks that obey the TPU block rule
    (minor block dim a multiple of 128 lanes, second-minor a multiple of the
    dtype's sublane tile, unless the block covers the whole padded problem
    dim), ``bk`` even for the FIP-family pair algebra (Eq. 2 consumes k in
    pairs, bk/2 pairs per lane block of each deinterleaved half), and the
    tile's planned VMEM (``ops.tile_vmem_bytes``) within the budget. The
    kernels pad non-divisible shapes, so divisibility of the problem shape is
    NOT a constraint;
  * flash attention: (bq, bk) power-of-2 sequence blocks; the head dim rides
    along untiled.

Ordering contract: the static default (what the code shipped with) is always
candidate 0, so a tuned schedule can only match or beat the default on the
machine that measured it; the remainder is ordered by log2 distance from the
default (nearest first, ties by ascending tuple) — a budget-limited run
explores the default's neighborhood, where the §5 sweep finds its optima,
before the far corners of the space. The order is deterministic either way.
"""
from __future__ import annotations

from typing import List, Tuple

from repro.kernels import ops as kops

Blocks = Tuple[int, int, int]

# Candidate axes: power-of-2, bounded to what the MXU/VPU tiling makes sane.
# bm reaches down to the f32 sublane tile (8) because serving decode GEMMs
# have M = batch_slots — tiny-M schedules are exactly what §5's sweep varies.
GEMM_BM = (8, 16, 32, 64, 128, 256)
GEMM_BN = (32, 64, 128, 256)
GEMM_BK_BASELINE = (32, 64, 128, 256, 512)
GEMM_BK_FIP = (8, 16, 32, 64, 128, 256, 512)   # even: Eq. 2 pairs
FLASH_BQ = (64, 128, 256)
FLASH_BK = (64, 128, 256)


def round_up_pow2(x: int, lo: int = 8) -> int:
    p = lo
    while p < x:
        p *= 2
    return p


def gemm_block_legal(bm: int, bn: int, bk: int, algo: str, m: int, n: int,
                     k: int, itemsize: int = 4) -> bool:
    """TPU legality of a (bm, bn, bk) block for an (m, k) x (k, n) ``algo``
    GEMM with ``itemsize``-byte operands."""
    def tiled(block: int, dim: int, mult: int) -> bool:
        return block % mult == 0 or block >= dim

    if min(bm, bn, bk) < 2:
        return False
    sublane = kops.SUBLANE * max(1, 4 // itemsize)   # packed dtypes: 16, 32
    if not (tiled(bm, m, sublane) and tiled(bn, n, kops.LANE)):
        return False
    if algo in ("fip", "ffip"):
        if bk % 2 or not tiled(bk // 2, -(-k // 2), kops.LANE):
            return False
    elif not tiled(bk, k, kops.LANE):
        return False
    return kops.tile_vmem_bytes(bm, bn, bk, algo, itemsize) <= kops._VMEM_BUDGET


def gemm_candidates(m: int, n: int, k: int, algo: str,
                    itemsize: int = 4) -> List[Blocks]:
    """Deterministically ordered legal candidates for an (m, k) x (k, n) GEMM.

    Blocks never exceed the pow2-rounded problem dims (a 256-wide block on a
    48-wide problem is pure padding waste), and the static default
    (ops.choose_blocks) always comes first.
    """
    bm_cap = round_up_pow2(m)
    bn_cap = round_up_pow2(n)
    bk_cap = round_up_pow2(k)
    bks = GEMM_BK_FIP if algo in ("fip", "ffip") else GEMM_BK_BASELINE
    cands = [
        (bm, bn, bk)
        for bm in GEMM_BM if bm <= bm_cap
        for bn in GEMM_BN if bn <= bn_cap
        for bk in bks if bk <= bk_cap
        if gemm_block_legal(bm, bn, bk, algo, m, n, k, itemsize)]
    default = tuple(kops.choose_blocks(m, n, k, algo))

    def dist(c):
        return sum(abs(x.bit_length() - d.bit_length())
                   for x, d in zip(c, default))

    return [default] + sorted((c for c in cands if c != default),
                              key=lambda c: (dist(c), c))


def conv_candidates(m: int, n: int, k: int, ckw: int, algo: str,
                    itemsize: int = 4) -> List[Blocks]:
    """Candidates for the fused implicit-im2col conv kernels.

    Same legality as the GEMM space, but the bk axis prefers MULTIPLES OF
    ``ckw`` = Cin_g * KW — one full kernel-window row of the flattened
    (kh, kw, cin) contraction axis per block, so a k-block's gather walks
    contiguous input rows (the §5.1.1 W-partitioning locality). For the
    FIP-family pair algebra bk must also be even: odd ``ckw`` contributes its
    even multiples only. Power-of-2 bk values stay in the space as the
    fallback (they are what ``ops.choose_blocks`` defaults to), and the
    static default remains candidate 0 — tuning can only match-or-beat it.
    """
    ckw = max(1, ckw)
    aligned = []
    mult = ckw
    # up to the first EVEN multiple that spans all of K (a single k block,
    # legal on TPU for any K)
    while mult <= max(GEMM_BK_BASELINE) and mult < k + 2 * ckw:
        if mult % 2 == 0 or algo == "baseline":
            aligned.append(mult)
        mult += ckw
    bks = GEMM_BK_FIP if algo in ("fip", "ffip") else GEMM_BK_BASELINE
    bk_cap = round_up_pow2(k, lo=2)
    bk_axis = sorted(set(list(aligned) + [b for b in bks if b <= bk_cap]))
    bm_cap = round_up_pow2(m)
    bn_cap = round_up_pow2(n)
    cands = [
        (bm, bn, bk)
        for bm in GEMM_BM if bm <= bm_cap
        for bn in GEMM_BN if bn <= bn_cap
        for bk in bk_axis
        if gemm_block_legal(bm, bn, bk, algo, m, n, k, itemsize)]
    default = tuple(kops.choose_blocks(m, n, k, algo))

    def dist(c):
        return sum(abs(x.bit_length() - d.bit_length())
                   for x, d in zip(c, default))

    return [default] + sorted((c for c in cands if c != default),
                              key=lambda c: (dist(c), c))


def flash_candidates(sq: int, sk: int) -> List[Tuple[int, int]]:
    """(bq, bk) candidates for flash attention; default (128, 128) first.
    The kernel clamps blocks to the (padded) sequence lengths itself."""
    bq_cap = round_up_pow2(sq, lo=min(FLASH_BQ))
    bk_cap = round_up_pow2(sk, lo=min(FLASH_BK))
    cands = sorted((bq, bk)
                   for bq in FLASH_BQ if bq <= bq_cap
                   for bk in FLASH_BK if bk <= bk_cap)
    default = (128, 128)
    return [default] + [c for c in cands if c != default]
