"""Fused implicit-im2col conv kernels — Algorithm 1 executed INSIDE the GEMM.

The paper's memory subsystem never materializes an im2col matrix: the §5.1
multi-digit address counters (Fig. 5) generate the conv->GEMM gather
addresses on the fly while the systolic array consumes the stream. The
``conv2d_via_gemm`` reference in :mod:`repro.core.im2col` models the counters
but still gathers the full (M, K) A matrix into HBM before calling a dense
GEMM. These kernels close that gap: the Algorithm-1 address arithmetic runs
*inside* the Pallas kernel, per (bm, bk) block —

    m digit -> (oh, ow) spatial position   (stride (sh, sw))
    k digit -> (kh, kw, cin-in-group)      (kernel offsets + channel)
    addr    = ((oh*sh + kh) * Wp + (ow*sw + kw)) * Cin + g*Cin_g + cin

— so the A matrix only ever exists as (bm, bk) VMEM tiles; HBM holds the
spatially-padded input exactly once. The arithmetic bodies mirror the GEMM
kernels (baseline dot / FIP pair algebra / FFIP y-delta prefix) operation for
operation, so for a fixed (bn, bk) a fused conv is BIT-IDENTICAL to running
the same Pallas GEMM over the materialized A — the reference oracle tests
rely on this.

Int8 path (§3.3/§4.4): :func:`prepare_quantized_conv` quantizes the filter
per output channel on the flattened KH*KW*Cin_g axis and precomputes the
Eq. 15 folded beta plus colsums; :func:`quantized_conv_apply` quantizes the
(spatially padded) input per tensor, runs the fused kernels on the raw int8
operands, and removes the zero-point terms with the Eq. 20 adjuster — the
row-sums come from a windowed reduction over the input, never from a
materialized A. Bit-exact against :func:`quantized_conv_reference`.

VMEM note: each grid step holds one padded input image in VMEM (the role the
paper's partitioned activation submemories play); full-resolution early VGG
layers exceed a real core's VMEM — the CPU CI runs interpret mode where this
is only a host buffer. Tiling the gather source is future work.
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Callable, Optional, Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.core import fip
from repro.kernels import compat
from repro.core.im2col import as_pair, conv_out_hw, Size2
from repro.kernels.compat import resolve_interpret, tpu_compiler_params
from repro.kernels.ffip_gemm import block_deltas, ffip_tile
from repro.kernels.fip_gemm import deinterleave, fip_tile, pad_to
from repro.kernels import ops as kops
from repro.obs import profile as _obs_profile

Array = jax.Array


@dataclasses.dataclass(frozen=True)
class ConvGeom:
    """Static conv geometry threaded into the kernels (hashable for jit).
    Batch rides in the array shapes, not here — the address arithmetic is
    per image."""
    h: int          # padded input height
    w: int          # padded input width
    cin: int
    kh: int
    kw: int
    sh: int
    sw: int
    groups: int
    ng: int         # output channels per group

    @property
    def cin_g(self) -> int:
        return self.cin // self.groups

    @property
    def oh(self) -> int:
        return conv_out_hw(self.h, self.w, self.kh, self.kw,
                           (self.sh, self.sw))[0]

    @property
    def ow(self) -> int:
        return conv_out_hw(self.h, self.w, self.kh, self.kw,
                           (self.sh, self.sw))[1]

    @property
    def m(self) -> int:
        return self.oh * self.ow

    @property
    def k(self) -> int:
        """Gather-valid contraction length KH*KW*Cin_g (the b-stack may carry
        an extra zero row when K is odd — evenized for the pair algebra)."""
        return self.kh * self.kw * self.cin_g


def _gather_tile(x_ref, g, mi, ki, *, bm: int, bk: int, geom: ConvGeom):
    """The in-kernel Algorithm-1 counter: materialize the (bm, bk) A tile for
    grid position (group g, m block mi, k block ki) by address arithmetic +
    gather from the flat padded image. k columns past the real K are zeroed
    (exact for the baseline products and the FIP pair algebra)."""
    m_idx = mi * bm + jax.lax.broadcasted_iota(jnp.int32, (bm, bk), 0)
    k_idx = ki * bk + jax.lax.broadcasted_iota(jnp.int32, (bm, bk), 1)
    # clamp padded rows/cols so addresses stay in range; masked/sliced later
    m_c = jnp.minimum(m_idx, geom.m - 1)
    k_c = jnp.minimum(k_idx, geom.k - 1)
    oh_i = m_c // geom.ow                        # spatial digits (m_offset)
    ow_i = m_c % geom.ow
    c_i = k_c % geom.cin_g                       # kernel digits (k_offset)
    rem = k_c // geom.cin_g
    kw_i = rem % geom.kw
    kh_i = rem // geom.kw
    row = oh_i * geom.sh + kh_i
    col = ow_i * geom.sw + kw_i
    addr = (row * geom.w + col) * geom.cin + g * geom.cin_g + c_i
    flat = x_ref[0]                              # (Hp*Wp*Cin,) in VMEM
    a = jnp.take(flat, addr.reshape(-1), axis=0).reshape(bm, bk)
    return jnp.where(k_idx < geom.k, a, jnp.zeros_like(a))


def _pair_halves(a):
    """Deinterleave a gathered (bm, bk) tile into the odd/even pair halves
    the FIP tiles take. These are strided lane slices, which Mosaic refuses,
    as it refuses the in-kernel gather above: until the gather source is
    tiled, the fused conv kernels run in interpret mode and raise when
    compiled for TPU."""
    return a[:, 0::2], a[:, 1::2]


def _conv_kernel(x_ref, *refs, acc_dtype, algo: str, fold_beta: bool,
                 bm: int, bk: int, geom: ConvGeom):
    """Grid (B, G, M/bm, N/bn, K/bk), K innermost. Mirrors baseline_gemm /
    fip_gemm / ffip_gemm exactly, with A gathered, not loaded. refs: the B
    block (baseline), its odd/even row halves (FIP) or those of the
    block-encoded y (FFIP), then the output block."""
    *b_refs, o_ref = refs
    g = pl.program_id(1)
    mi = pl.program_id(2)
    ki = pl.program_id(4)
    a = _gather_tile(x_ref, g, mi, ki, bm=bm, bk=bk, geom=geom)
    if algo == "baseline":
        part = jax.lax.dot_general(a, b_refs[0][0], (((1,), (0,)), ((), ())),
                                   preferred_element_type=acc_dtype)
    else:                                        # shared Eq. (2) / (7) algebra
        tile = fip_tile if algo == "fip" else ffip_tile
        part = tile(*_pair_halves(a.astype(acc_dtype)),
                    b_refs[0][0].astype(acc_dtype),
                    b_refs[1][0].astype(acc_dtype), fold_beta=fold_beta)

    @pl.when(ki == 0)
    def _init():
        o_ref[...] = part[None, None]

    @pl.when(ki != 0)
    def _acc():
        o_ref[...] += part[None, None]


@functools.partial(jax.jit, static_argnames=("geom", "algo", "bm", "bn", "bk",
                                             "interpret", "fold_beta"))
def _fused_flat(xf: Array, bg: Array, *, geom: ConvGeom, algo: str, bm: int,
                bn: int, bk: int, interpret: bool, fold_beta: bool) -> Array:
    """xf: (B, Hp*Wp*Cin) flat padded input; bg: (G, Ks, Ng) weights (or y
    deltas for ffip) -> (B, G, Mp, Np) accumulator-dtype output."""
    n_b, length = xf.shape
    n_g, ks, ng = bg.shape
    acc_dtype = (jnp.int32 if jnp.issubdtype(xf.dtype, jnp.integer)
                 else jnp.float32)
    mp = -(-geom.m // bm) * bm
    kp = -(-ks // bk) * bk
    np_ = -(-ng // bn) * bn
    if algo == "baseline":
        dtype = jnp.result_type(xf.dtype, bg.dtype)
        xf = xf.astype(dtype)
        b_ops = [pad_to(bg.astype(dtype), (n_g, kp, np_))]
        b_block = (1, bk, bn)
    else:                                # FIP family: deinterleaved halves
        if algo == "ffip":
            bg = block_deltas(pad_to(bg, (n_g, ks, np_)), bn)
        b_ops = [pad_to(h, (n_g, kp // 2, np_)) for h in deinterleave(bg, 1)]
        b_block = (1, bk // 2, bn)
    return pl.pallas_call(
        functools.partial(_conv_kernel, acc_dtype=acc_dtype, algo=algo,
                          fold_beta=fold_beta, bm=bm, bk=bk, geom=geom),
        grid=(n_b, n_g, mp // bm, np_ // bn, kp // bk),
        in_specs=[pl.BlockSpec((1, length), lambda bi, g, i, j, kk: (bi, 0))]
        + [pl.BlockSpec(b_block, lambda bi, g, i, j, kk: (g, kk, j))]
        * len(b_ops),
        out_specs=pl.BlockSpec((1, 1, bm, bn),
                               lambda bi, g, i, j, kk: (bi, g, i, j)),
        out_shape=jax.ShapeDtypeStruct((n_b, n_g, mp, np_), acc_dtype),
        compiler_params=tpu_compiler_params(dimension_semantics=(
            "parallel", "parallel", "parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(xf, *b_ops)


# Offline weight derivations (stack / evenize / y-deltas) memoize through the
# shared compat.derived cache (one id+weakref+tracer-bypass implementation for
# this module and ffip_gemm). Without it every eager FFIP conv forward would
# re-encode its filters (§4.4 says y is an OFFLINE transform of the weights).
def _derived(tag: str, arr: Array, fn: Callable[[Array], Array]) -> Array:
    return compat.derived.get(tag, arr, fn)


def _kernel_to_stack(kernel: Array, groups: int) -> Array:
    """(KH, KW, Cin_g, Cout) -> (G, KH*KW*Cin_g, Cout/G): the per-group B
    operands on the flattened (kh, kw, cin) contraction axis."""
    kh, kw, cin_g, cout = kernel.shape
    if cout % groups:
        raise ValueError(f"cout={cout} not divisible by groups={groups}")
    ng = cout // groups
    b2 = kernel.reshape(kh * kw * cin_g, cout)
    return jnp.moveaxis(b2.reshape(kh * kw * cin_g, groups, ng), 1, 0)


def _evenize_k(bg: Array) -> Array:
    """Zero-pad the contraction axis to even length (the FIP pair algebra
    consumes K in pairs; a zero row pairs exactly — mixed pairs reduce to the
    plain product term)."""
    if bg.shape[1] % 2:
        bg = jnp.pad(bg, ((0, 0), (0, 1), (0, 0)))
    return bg


def fused_conv_raw(x: Array, bg: Array, *, kh: int, kw: int,
                   stride: Size2 = 1, groups: int = 1, algo: str = "ffip",
                   bm: int = 0, bn: int = 0, bk: int = 0,
                   interpret: Optional[bool] = None,
                   fold_beta: bool = False) -> Array:
    """Raw fused conv on an ALREADY spatially-padded input.

    x: (B, Hp, Wp, Cin) (any float or int dtype); bg: (G, Ks, Ng) per-group
    weight stack on the flattened (kh, kw, cin_g) axis (Ks may be the
    evenized K). Returns (B, OH, OW, Cout) in the accumulation dtype
    (int32 for ints, float32 for floats) — callers cast/rescale.
    """
    interpret = resolve_interpret(interpret)
    n_b, h, w, cin = x.shape
    sh, sw = as_pair(stride)
    n_g, ks, ng = bg.shape
    if n_g != groups:
        raise ValueError(f"b-stack has {n_g} groups, expected {groups}")
    geom = ConvGeom(h=h, w=w, cin=cin, kh=kh, kw=kw, sh=sh, sw=sw,
                    groups=groups, ng=ng)
    if ks not in (geom.k, geom.k + geom.k % 2):
        raise ValueError(f"b-stack K={ks} does not match KH*KW*Cin_g={geom.k}")
    if algo == "ffip":
        # evenize + Eq. 9 y-delta encoding per group — an offline transform
        # of the weights (§4.4), memoized per source array like the GEMM path
        bg = _derived("y_even", bg,
                      lambda b: jax.vmap(fip.make_y)(_evenize_k(b)))
        ks = bg.shape[1]
    elif algo == "fip":
        bg = _derived("even", bg, _evenize_k)
        ks = bg.shape[1]
    if not (bm and bn and bk):
        bm, bn, bk = kops.choose_blocks(geom.m, ng, ks, algo)
    if algo in ("fip", "ffip") and bk % 2:
        raise ValueError(f"bk={bk} must be even for the FIP pair algebra")
    xf = x.reshape(n_b, h * w * cin)
    out = _fused_flat(xf, bg, geom=geom, algo=algo, bm=bm, bn=bn, bk=bk,
                      interpret=interpret, fold_beta=fold_beta)
    out = out[:, :, :geom.m, :ng]                        # (B, G, M, Ng)
    out = jnp.moveaxis(out, 1, 2).reshape(n_b, geom.oh, geom.ow, groups * ng)
    return out


def conv_gemm_fused(x: Array, kernel: Array, *, stride: Size2 = 1,
                    pad: Size2 = 0, groups: int = 1, algo: str = "ffip",
                    bm: int = 0, bn: int = 0, bk: int = 0,
                    interpret: Optional[bool] = None) -> Array:
    """NHWC conv via the fused implicit-im2col kernels (float front door).

    x: (B, H, W, Cin); kernel: (KH, KW, Cin/groups, Cout). Drop-in for
    :func:`repro.core.im2col.conv2d_via_gemm` — same (B, OH, OW, Cout)
    result, but the im2col matrix never exists outside VMEM tiles.
    """
    ph, pw = as_pair(pad)
    if ph or pw:
        x = jnp.pad(x, ((0, 0), (ph, ph), (pw, pw), (0, 0)))
    kh, kw, _, _ = kernel.shape
    sh, sw = as_pair(stride)
    _obs_profile.on_conv(x, kernel, oh=(x.shape[1] - kh) // sh + 1,
                         ow=(x.shape[2] - kw) // sw + 1, groups=groups,
                         algo=algo)
    bg = _derived(f"stack{groups}", kernel,
                  lambda k_: _kernel_to_stack(k_, groups))
    out = fused_conv_raw(x, bg, kh=kh, kw=kw, stride=stride, groups=groups,
                         algo=algo, bm=bm, bn=bn, bk=bk, interpret=interpret)
    if jnp.issubdtype(x.dtype, jnp.integer):
        return out                                   # int32 accumulator
    return out.astype(jnp.result_type(x.dtype, kernel.dtype))


# ---------------------------------------------------------------------------
# Quantized conv (§3.3/§4.4 on the flattened KH*KW*Cin_g axis)
# ---------------------------------------------------------------------------

def prepare_quantized_conv(kernel: Array, *, groups: int = 1,
                           dtype=jnp.int8) -> dict:
    """Offline filter quantization for the int8 conv path.

    kernel: (KH, KW, Cin/groups, Cout). Quantizes per output channel on the
    flattened KH*KW*Cin_g contraction axis via
    :func:`repro.core.quant.prepare_quantized_dense` (so the conv path
    inherits the Eq. 15 folded beta and the colsum terms), with the K axis
    zero-evenized for the pair algebra. Returns the per-group stacked dict
    plus the conv bookkeeping (k_real, kh, kw, groups).
    """
    from repro.core import quant
    kh, kw, cin_g, cout = kernel.shape
    bg = _kernel_to_stack(kernel, groups)            # (G, K, Ng) float
    bg = _evenize_k(bg)
    q = quant.prepare_quantized_dense(bg, dtype=dtype)
    q.update(k_real=kh * kw * cin_g, kh=kh, kw=kw, groups=groups)
    return q


def quantize_input_per_tensor(xp: Array) -> Tuple[Array, Array, Array]:
    """Per-tensor asymmetric int8 quantization of a spatially PADDED input
    (pad first: real 0.0 then quantizes exactly to the zero point, so border
    windows stay faithful). Returns (xq int8, scale f32, zero_point i32)."""
    x32 = xp.astype(jnp.float32)
    xmin = jnp.minimum(jnp.min(x32), 0.0)
    xmax = jnp.maximum(jnp.max(x32), 0.0)
    scale = jnp.maximum((xmax - xmin) / 255.0, 1e-12)
    zp = jnp.clip(jnp.round(-128 - xmin / scale), -128, 127).astype(jnp.int32)
    xq = jnp.clip(jnp.round(x32 / scale) + zp, -128, 127).astype(jnp.int8)
    return xq, scale, zp


def conv_rowsums(xq: Array, *, kh: int, kw: int, stride: Size2,
                 groups: int = 1) -> Array:
    """rowsum(A_q) for the implicit im2col matrix, per group, WITHOUT
    materializing A: sum the (already padded, already quantized) input over
    each group's channels, then box-reduce over the kernel window.
    xq: (B, Hp, Wp, Cin) -> (B, OH, OW, G) int32 — the Eq. 20 adjuster input.
    """
    sh, sw = as_pair(stride)
    n_b, h, w, cin = xq.shape
    cin_g = cin // groups
    xs = xq.astype(jnp.int32).reshape(n_b, h, w, groups, cin_g).sum(-1)
    return jax.lax.reduce_window(
        xs, jnp.int32(0), jax.lax.add,
        window_dimensions=(1, kh, kw, 1), window_strides=(1, sh, sw, 1),
        padding="VALID")


def quantized_conv_apply(x: Array, q: dict, *, stride: Size2 = 1,
                         pad: Size2 = 0, algo: str = "ffip",
                         bm: int = 0, bn: int = 0, bk: int = 0,
                         interpret: Optional[bool] = None) -> Array:
    """Int8 conv through offline-prepared weights, fused implicit im2col.

    Mirrors ``core.quant.quantized_dense_apply`` with the hardware's conv
    strategy: raw (F)FIP on the quantized integers (both-signed, d=1, beta
    folded offline per Eq. 15), zero-point contributions removed via the
    Eq. 20 adjuster with windowed row-sums and the offline colsums. Returns
    float32 (B, OH, OW, Cout) ~= conv(x, w).
    """
    ph, pw = as_pair(pad)
    if ph or pw:
        x = jnp.pad(x, ((0, 0), (ph, ph), (pw, pw), (0, 0)))
    xq, a_scale, a_zp = quantize_input_per_tensor(x)
    groups, kh, kw = q["groups"], q["kh"], q["kw"]
    fold = algo in ("fip", "ffip")
    raw = fused_conv_raw(xq, q["qw"], kh=kh, kw=kw, stride=stride,
                         groups=groups, algo=algo, bm=bm, bn=bn, bk=bk,
                         interpret=interpret, fold_beta=fold)
    return _dequantize_conv(raw, xq, q, a_scale, a_zp, stride=stride,
                            fold_beta=fold)


def _dequantize_conv(raw: Array, xq: Array, q: dict, a_scale: Array,
                     a_zp: Array, *, stride: Size2, fold_beta: bool) -> Array:
    """Shared epilogue: folded beta + zero-point corrections + rescale.
    raw: (B, OH, OW, Cout) int32 = A_q W_q (cross - alpha when fold_beta)."""
    groups, kh, kw = q["groups"], q["kh"], q["kw"]
    ng = q["qw"].shape[-1]
    n_b = raw.shape[0]
    oh, ow = raw.shape[1], raw.shape[2]
    acc = raw.reshape(n_b, oh, ow, groups, ng)
    if fold_beta:
        acc = acc + q["neg_beta"]                    # Eq. 15: + (-beta(W_q))
    rs = conv_rowsums(xq, kh=kh, kw=kw, stride=stride, groups=groups)
    acc = (acc
           - a_zp * q["colsum"]                      # za * colsum(W_q)
           - rs[..., None] * q["zp"]                 # Eq. 20: zb_j * rowsum(A)_i
           + q["k_real"] * a_zp * q["zp"])
    out = acc.astype(jnp.float32) * (a_scale * q["scale"])
    return out.reshape(n_b, oh, ow, groups * ng)


def quantized_conv_reference(x: Array, q: dict, *, stride: Size2 = 1,
                             pad: Size2 = 0, algo: str = "ffip") -> Array:
    """Materializing oracle for :func:`quantized_conv_apply`: gathers the
    full A_q via the Algorithm-1 indices (core.im2col) and runs the same
    integer algebra through the core.fip closed forms. Bit-identical to the
    fused path for every legal block choice (int32 addition is exact)."""
    from repro.core.im2col import conv_gemm_indices
    ph, pw = as_pair(pad)
    if ph or pw:
        x = jnp.pad(x, ((0, 0), (ph, ph), (pw, pw), (0, 0)))
    xq, a_scale, a_zp = quantize_input_per_tensor(x)
    groups, kh, kw = q["groups"], q["kh"], q["kw"]
    n_b, h, w, cin = xq.shape
    sh, sw = as_pair(stride)
    oh, ow = conv_out_hw(h, w, kh, kw, (sh, sw))
    flat = xq.reshape(n_b, h * w * cin)
    zero_bias = jnp.zeros((), jnp.int32)             # beta re-added in epilogue
    raws = []
    for g in range(groups):
        idx = jnp.asarray(conv_gemm_indices(h, w, cin, kh, kw, (sh, sw),
                                            groups=groups, group=g))
        aq = flat[:, idx].astype(jnp.int32)          # (B, M, K) materialized
        if aq.shape[-1] < q["qw"].shape[1]:          # evenized weight K
            aq = jnp.pad(aq, ((0, 0), (0, 0),
                              (0, q["qw"].shape[1] - aq.shape[-1])))
        b32 = q["qw"][g].astype(jnp.int32)
        if algo == "baseline":
            raws.append(jnp.matmul(aq, b32))
        elif algo == "ffip":
            raws.append(fip.fip_matmul_beta_folded(
                fip.pair_swap(aq), fip.pair_swap_rows(b32), zero_bias))
        else:
            raws.append(fip.fip_matmul_beta_folded(aq, b32, zero_bias))
    raw = jnp.stack(raws, axis=1)                    # (B, G, M, Ng)
    ng = q["qw"].shape[-1]
    raw = jnp.moveaxis(raw, 1, 2).reshape(n_b, oh, ow, groups * ng)
    return _dequantize_conv(raw, xq, q, a_scale, a_zp, stride=stride,
                            fold_beta=(algo != "baseline"))
