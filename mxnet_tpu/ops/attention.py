"""Flash attention — Pallas TPU kernel.

NEW capability relative to the reference (SURVEY.md §5.7: the transformer
era postdates MXNet 0.12; nothing like this exists there).  This is the
TPU answer to the reference's cuDNN-fused kernels: an online-softmax
blocked attention whose QK^T and PV matmuls tile onto the MXU and whose
working set stays in VMEM — O(S) memory instead of the O(S²) a naive
softmax(QK^T)V materializes.

The backward pass (_flash_bwd below) is one Pallas kernel in the FA2
style that recomputes the attention probabilities blockwise from the
forward's saved logsumexp and feeds dK, dV and dQ from the same tile —
O(S) memory end-to-end, with GQA/MQA handled at the block-spec level so
repeated KV heads are never materialized.  Where its whole-sequence dQ
accumulator does not fit VMEM (_geometry decides from the shapes), dQ
gets a kernel of its own.

Where the kernels run.  A process pinned to the CPU platform
(``JAX_PLATFORMS=cpu`` — the unit tests) runs them in Pallas interpret
mode; everywhere else they compile through Mosaic, and a compile error is
an error (nothing falls back to the XLA reference).  Both trace the SAME
program — the same tiles (derived from the shapes by _geometry on every
backend), the same sequence padding and index maps, traced with 64-bit
types off — so the CPU tests cover everything but Mosaic itself; they
force 128-row tiles where they want several tiles at a small S.  The
Pallas->Mosaic lowering is covered without a chip by
tests/test_attention.py's cross-lowering tests, and Mosaic's own compile
and the numbers it produces by chip_smoke.py on the chip (SURVEY.md §4
device-consistency strategy).
"""
from __future__ import annotations

import functools
import math
from typing import NamedTuple

import jax
import jax.numpy as jnp
from jax import lax

from .. import base, tracing
from .registry import register

_NEG_INF = -1e30
_LANES = 128     # minor (lane) tile of a TPU vector register
_SUBLANES = 8    # second-minor tile for 32-bit types


def _pick_interpret():
    """Interpret the kernels only where the process was pinned to the CPU
    on purpose (context.platform_pinned_to_cpu); anything else compiles
    them — a machine that merely lacks a TPU gets the compile error, not
    a silent interpreter."""
    from ..context import platform_pinned_to_cpu
    return platform_pinned_to_cpu()


def _x32(fn):
    """Trace ``fn`` with 64-bit types off.  The package enables x64 at
    import (base.py), under which every Python int that meets a traced
    int32 inside a jnp function (``b // G`` in an index map, a loop
    bound) enters as int64 — and Mosaic has no 64-bit types."""
    @functools.wraps(fn)
    def wrapped(*args, **kwargs):
        with jax.enable_x64(False):
            return fn(*args, **kwargs)
    return wrapped


def _round_up(x, m):
    return -(-x // m) * m


# Preferred rows (q, k) of a score tile, from the ladders on the v5e
# (B 4, H 16, S 1024, D 64, causal, bf16, device ms a call; PERF.md §6,
# PR 28 and PR 31).  A tile's time is its serial chain (matmul, reduce,
# exp, matmul, rescale), which wide tiles amortise; past 512 rows the
# backward's causal tile pairs stop being skipped and it loses again:
#   flash_fwd         128x128 1.247, 512x512 0.366, 1024x1024 0.213
#   flash_bwd_dkv_dq  128x128 1.482, 256x256 0.579, 256x512 0.467,
#                     512x256 0.468, 512x512 0.418, 512x1024 0.503,
#                     1024x512 0.505, 1024x1024 0.510
#   flash_bwd_dq + flash_bwd_dkv (where dQ's accumulator does not fit)
#                     128x128 1.121 + 0.965, 512x512 0.268 + 0.325,
#                     1024x1024 0.289 + 0.413
# 512x512 is also the merged kernel's best at S 4096 (1.215; 1024x512
# 1.251, 512x1024 1.241) and at D 128 with 4 query heads a KV head (0.103;
# 0.125, 0.123); not causal, 512x1024 reads 0.491 for 0.526.
_TILE_FWD = (1024, 1024)
_TILE_BWD = (512, 512)
# Rows r of a strip of a causal diagonal tile (0: the diagonal tile
# masked whole): a forward tile of up to 512 rows (``fwd``), a wider one
# (``fwd_wide``, only in a sequence of more than one tile), a backward
# tile (``bwd``).  Strip s of b / r meets only the (s + 1) r keys
# (forward) or b - s r queries (backward) on its side of the diagonal
# and masks only its r x r block on it.  From the ladders on the v5e
# (bf16, causal, device ms a call, masked tile -> strips of 128 / 256 /
# 512; PERF.md §6, PR 38):
#   flash_fwd, 1024-row tiles  S 1024 D 64 (one tile) 0.2145 -> 0.2659 /
#                     0.2674 / 0.2746; S 1024 D 128 GQA 8/2 0.1028 ->
#                     0.1327 / 0.1339 / 0.1352; S 2048 D 64 0.8140 ->
#                     0.8583 / 0.7663 / 0.7242; S 4096 D 128 0.6840 ->
#                     0.7034 / 0.6564 / 0.6345; S 1100 (640 rows) 0.4629
#                     -> 0.5154 (128 only)
#   flash_fwd, one tile of up to 512 rows  S 512 D 64 0.1401 -> 0.0917 /
#                     0.1348; B 16 0.5656 -> 0.3724 (128); GQA 8/2 D 128
#                     0.0715 -> 0.0476 / 0.0687; S 384 0.0946 -> 0.0696,
#                     S 256 0.0721 -> 0.0600 (128); 512-row tiles forced
#                     at S 1024 0.3724 -> 0.3616 / 0.3802, at S 4096 D 128
#                     1.1369 -> 1.1242 (128)
#   flash_bwd_dkv_dq, 512-row  S 1024 D 64 0.4180 -> 0.3545 / 0.3748 /
#                     0.4174; S 4096 D 128 1.2354 -> 1.1689 / 1.1876 /
#                     1.2301; GQA 8/2 D 128 0.2044 -> 0.1740 / 0.1834 /
#                     0.2039; S 2048 1.4085 -> 1.2830 / 1.3244 / 1.4102;
#                     S 1100 (384 rows) 0.5559 -> 0.5237; S 512 0.1465 ->
#                     0.1090 / 0.1211 (128)
# At one 1024-row tile every width loses (not understood: §7).  The
# preferred tiles stay: the forward at 512 rows is the row above, the
# backward at 1024 rows in strips of 128 reads 0.3160 at S 1024 D 64 but
# needs the two dQ and dK/dV kernels from S 2048 or GQA 8/2 D 128 on
# (2 x 0.9172, 2 x 0.1236): a rule for it is open (§7).
_DIAG_STRIP = {"fwd": 128, "fwd_wide": 512, "bwd": 128}
# What a grid step may take of VMEM by _vmem_bytes' count.  Mosaic grants
# a kernel 16 MiB unless told otherwise, and nothing here asks for more.
_VMEM_BUDGET = 14 << 20


class _Geometry(NamedTuple):
    """Static sizes of one call, shared by its kernels."""
    B: int
    H: int
    Hk: int
    G: int
    Sq: int
    Sk: int
    D: int
    block_q: int
    block_k: int
    Sqp: int
    Skp: int
    nq: int
    nk: int
    vmem_bytes: int   # _vmem_bytes of these tiles
    derived: bool     # no explicit block was given
    merged: bool      # backward: one kernel, dQ accumulated in VMEM
    diag_strip: int   # causal, square on the diagonal: its strips' rows


def _vmem_bytes(block_q, block_k, D, itemsize, forward, dq_rows=0):
    """VMEM one grid step takes, by count — of ``flash_fwd``, or of the
    backward: every in and out block twice (the pipeline's two buffers),
    the f32 scratch, and two f32 score tiles for s, p, dp, ds and their
    masks (compiled for a v5e with Mosaic's limit lowered until it
    refused, the kernels needed blocks + scratch + at most 1.2 tiles;
    PERF.md §6, PR 26).  ``dq_rows`` > 0 counts the merged backward
    kernel, which keeps dQ of one KV head's whole group (G * Sqp rows)
    resident as an out block and an f32 accumulator; 0 the hungrier of
    the two kernels it falls back to.  A block's minor dim pads to 128
    lanes."""
    Dp = _round_up(D, _LANES)
    row = Dp * itemsize                  # one row of q/k/v/o/do
    tile = block_q * block_k * 4
    if forward:
        blocks = (2 * block_q + 2 * block_k) * row \
            + block_q * _LANES * 4                         # q o, k v, lse
        scratch = block_q * (2 * _LANES + Dp) * 4          # m l, acc
        return 2 * blocks + scratch + 2 * tile
    stats = 2 * _SUBLANES * block_q * 4                    # lse, delta rows
    dkv = (2 * block_q + 4 * block_k) * row + stats        # q do, k v dk dv
    if dq_rows:
        blocks = dkv + dq_rows * row                       # ... and all dq
        scratch = (2 * block_k + dq_rows) * Dp * 4         # dk dv, dq acc
        return 2 * blocks + scratch + 2 * tile
    dq = (3 * block_q + 2 * block_k) * row + stats         # q do dq, k v
    scratch = max(block_q, 2 * block_k) * Dp * 4           # dq | dk dv acc
    return 2 * max(dq, dkv) + scratch + 2 * tile


def _tile_rows(S, block, preferred):
    """Rows of a tile along a sequence of S: a lane multiple (the score
    tile and the rows of per-query statistics are both lane-dense).  An
    explicit ``block`` is taken as given; derived, it is the fewest equal
    tiles of at most ``preferred`` rows (S 1100 at 512 is 3 x 384, not
    3 x 512: padding stays under one lane tile a block).  Either way a
    shorter sequence is one tile of its own padded length."""
    if block is None:
        block = _round_up(-(-S // -(-S // preferred)), _LANES)
    elif block % _LANES:
        raise ValueError(
            f"flash attention blocks must be multiples of {_LANES}, got "
            f"{block}")
    return min(block, _round_up(S, _LANES))


def _strip_rows(block, preferred):
    """Rows of a diagonal tile's strips: ``preferred`` where it divides
    ``block`` (a 640-row tile has no strips of 512), else 0."""
    return preferred if preferred and block % preferred == 0 else 0


def _geometry(q, k, block_q, block_k, forward, causal=False):
    """The one place that sizes a call: the tiles from what it can see —
    the two sequence lengths, the head dim, the operand dtype, the query
    heads a KV head — for ``flash_fwd`` (``forward``) or for the backward.
    Derived tiles start from _TILE_FWD / _TILE_BWD and the wider side is
    halved until _vmem_bytes fits _VMEM_BUDGET (float32 operands, a wide
    head); explicit blocks (tests) must be lane multiples and must fit as
    given.  The backward is the merged kernel where its count fits,
    dQ's whole-sequence accumulator included; where it does not (S 8192, D 128, four query heads a KV
    head: 33 MB), the same tiles are counted for the dQ and dK/dV
    kernels apart.  Sequences are padded to whole tiles; the head dim is
    a whole block dim and travels unpadded.  A ``causal`` call with
    Sq == Sk whose tiles came out square has every tile at a static
    offset from the diagonal: its diagonal tiles run in strips of
    ``diag_strip`` rows (_DIAG_STRIP, _strip_rows) and the tiles below
    them unmasked — a forward tile of over 512 rows only where the
    sequence is more than one tile; any other call has ``diag_strip``
    0.  All of it on every backend, so the interpreted CPU tests trace
    the program the chip compiles."""
    B, H, Sq, D = q.shape
    Hk, Sk = k.shape[1], k.shape[2]
    if H % Hk:
        raise ValueError(f"q heads {H} not divisible by kv heads {Hk}")
    G = H // Hk
    itemsize = jnp.dtype(q.dtype).itemsize
    derived = block_q is None and block_k is None
    pref_q, pref_k = _TILE_FWD if forward else _TILE_BWD
    while True:
        bq = _tile_rows(Sq, block_q, pref_q)
        bk = _tile_rows(Sk, block_k, pref_k)
        Sqp = _round_up(Sq, bq)
        vmem = _vmem_bytes(bq, bk, D, itemsize, forward)
        merged = False
        if not forward:
            with_dq = _vmem_bytes(bq, bk, D, itemsize, forward, G * Sqp)
            merged = with_dq <= _VMEM_BUDGET
            if merged:
                vmem = with_dq
        if vmem <= _VMEM_BUDGET:
            break
        if not derived:
            raise ValueError(
                f"flash attention blocks {bq} x {bk} (head dim {D}, "
                f"{itemsize}-byte operands) count {vmem} bytes of VMEM a "
                f"grid step, over the budget of {_VMEM_BUDGET}")
        if bq == bk == _LANES:
            break           # nothing left to narrow: Mosaic has the say
        if bk >= bq:
            pref_k = bk // 2
        else:
            pref_q = bq // 2
    Skp = _round_up(Sk, bk)
    strip = 0
    if causal and Sq == Sk and bq == bk:
        if not forward:
            strip = _strip_rows(bq, _DIAG_STRIP["bwd"])
        elif bq <= 512:
            strip = _strip_rows(bq, _DIAG_STRIP["fwd"])
        elif Sqp > bq:
            strip = _strip_rows(bq, _DIAG_STRIP["fwd_wide"])
    return _Geometry(B, H, Hk, G, Sq, Sk, D, bq, bk, Sqp, Skp,
                     Sqp // bq, Skp // bk, vmem, derived, merged, strip)


def _tile_kinds(geo, causal, strip):
    """The live score tiles of one head by kind: below the diagonal and
    unmasked (``tiles_full``), on it in strips (``tiles_diag``), or masked
    whole by _tile_mask (``tiles_masked``: every live tile of a causal
    call that is not stripped, the last k tile's of one with K padding)."""
    full = diag = masked = 0
    if strip:
        full, diag = geo.nq * (geo.nq - 1) // 2, geo.nq
    elif causal:
        bq, bk = geo.block_q, geo.block_k
        masked = sum(min((i * bq + bq - 1) // bk, geo.nk - 1) + 1
                     for i in range(geo.nq))
    elif geo.Skp != geo.Sk:
        masked = geo.nq
    return {"tiles_full": full, "tiles_diag": diag, "tiles_masked": masked}


def _say_geometry(kernel, geo, causal, grid, strip, **more):
    """One trace instant a compile (this runs while jit traces the
    kernel's caller, never per call): what engaged, for whoever reads the
    kernel's time beside it.  ``strip``: the diagonal strips' rows this
    kernel runs (0: none)."""
    tracing.instant("mx.attention.geometry", "attention", args={
        "kernel": kernel, "Sq": geo.Sq, "Sk": geo.Sk, "D": geo.D,
        "G": geo.G, "causal": bool(causal), "block_q": geo.block_q,
        "block_k": geo.block_k, "grid": list(grid),
        "grid_steps": math.prod(grid), "vmem_bytes": geo.vmem_bytes,
        "derived": geo.derived, "diag_strip": strip,
        **_tile_kinds(geo, causal, strip), **more})


def _pad_heads(x, Sp):
    """(B, h, S, D) -> (B*h, Sp, D), rows zero padded."""
    B, h, S, D = x.shape
    return jnp.pad(x, ((0, 0), (0, 0), (0, Sp - S), (0, 0))) \
        .reshape(B * h, Sp, D)


def _compiler_params(second="parallel"):
    from jax.experimental.pallas import tpu as pltpu
    # the last grid axis walks the reduction (scratch accumulators carry
    # across it); the first is independent programs, and so is the second
    # unless the caller carries state across it too
    return pltpu.CompilerParams(
        dimension_semantics=("parallel", second, "arbitrary"))


def _tile_mask(q_lo, k_lo, shape, Sk, Skp, causal, q_axis):
    """Validity of one score tile, or None when every entry is valid.
    ``q_axis`` is the tile axis that walks query rows (0 for the
    (block_q, block_k) tile, 1 for its transpose)."""
    valid = None
    if Skp != Sk or causal:
        k_pos = k_lo + lax.broadcasted_iota(jnp.int32, shape, 1 - q_axis)
        if Skp != Sk:
            valid = k_pos < Sk                      # mask K padding
        if causal:
            q_pos = q_lo + lax.broadcasted_iota(jnp.int32, shape, q_axis)
            c = k_pos <= q_pos
            valid = c if valid is None else valid & c
    return valid


def _mask_cols(s, valid, lo):
    """``s`` with its columns [lo, lo + width of ``valid``) masked by
    ``valid`` and the others as they are: a diagonal strip builds its
    mask over its one r x r block on the diagonal, not over the strip."""
    hi = lo + valid.shape[1]
    parts = [jnp.where(valid, s[:, lo:hi], _NEG_INF)]
    if lo:
        parts.insert(0, s[:, :lo])
    if hi < s.shape[1]:
        parts.append(s[:, hi:])
    return jnp.concatenate(parts, axis=1) if len(parts) > 1 else parts[0]


def _kv_spec(pl, D, G, block_q, block_k, causal):
    """K/V BlockSpec of a (B*H, q blocks, k blocks) grid: program b walks
    q heads and its KV head is b // G (GQA sharing).  Causal: k blocks
    strictly above the diagonal contribute nothing; their programs are
    skipped (``last_k``) and re-name the last needed block, so no DMA is
    issued for them.  Of the live ones, where _geometry gave the call a
    ``diag_strip``, a tile below the diagonal runs with no mask and the
    one on it in strips; otherwise each is masked whole (_tile_mask).
    Returns (spec, last_k)."""
    def last_k(i):
        return (i * block_q + block_q - 1) // block_k

    if causal:
        def kv_map(b, i, j):
            return (b // G, jnp.minimum(j, last_k(i)), 0)
    else:
        def kv_map(b, i, j):
            return (b // G, j, 0)
    return pl.BlockSpec((1, block_k, D), kv_map), last_k


@functools.partial(jax.jit, static_argnames=("causal", "scale", "block_q",
                                             "block_k", "interpret",
                                             "return_lse"))
@_x32
def _flash_fwd(q, k, v, causal=False, scale=None, block_q=None,
               block_k=None, interpret=None, return_lse=False):
    """q: (B, H, Sq, D); k/v: (B, Hk, Sk, D) with Hk dividing H (GQA/MQA:
    each group of H/Hk query heads shares one KV head — the kernel maps
    query-head programs onto the shared KV block, so grouped KV is NEVER
    materialized at H heads) → (B, H, Sq, D)
    [, lse (B, H, Sq) when return_lse — consumed by the Pallas backward].

    Grid (B*H, q blocks, k blocks): one (block_q, D) query tile meets one
    (block_k, D) K/V tile per program, so VMEM residency is set by the
    block sizes (_geometry derives them from the shapes; block_q/block_k
    force them) and not by the sequence length; the online-softmax state
    (m, l, acc) lives in VMEM scratch across the k axis.  Causal with a
    ``diag_strip`` r (_geometry): a tile below the diagonal runs with no
    mask, and the diagonal tile as b / r strips, strip s taking query
    rows [s r, (s + 1) r) against keys [0, (s + 1) r) only, masked on
    its last r x r block, into the same rows of m, l and acc."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    geo = _geometry(q, k, block_q, block_k, forward=True, causal=causal)
    (B, H, Hk, G, Sq, Sk, D, block_q, block_k, Sqp, Skp, nq,
     nk, *_) = geo
    strip = geo.diag_strip
    grid = (B * H, nq, nk)
    _say_geometry("flash_fwd", geo, causal, grid, strip)
    if scale is None:
        scale = 1.0 / (D ** 0.5)
    if interpret is None:
        interpret = _pick_interpret()

    kv_spec, last_k = _kv_spec(pl, D, G, block_q, block_k, causal)

    def kernel(q_ref, k_ref, v_ref, o_ref, *rest):
        lse_ref = rest[0] if return_lse else None
        m_ref, l_ref, acc_ref = rest[-3:]
        i, j = pl.program_id(1), pl.program_id(2)

        @pl.when(j == 0)
        def _():
            m_ref[...] = jnp.full(m_ref.shape, _NEG_INF, jnp.float32)
            l_ref[...] = jnp.zeros(l_ref.shape, jnp.float32)
            acc_ref[...] = jnp.zeros(acc_ref.shape, jnp.float32)

        def step(mask, rows=slice(None)):
            """Query rows ``rows`` of the tile against the keys they
            meet (a strip's: as many as it has rows past the tile's
            start), their scores through ``mask``."""
            kv = slice(None) if rows.stop is None else slice(0, rows.stop)
            s = lax.dot_general(
                q_ref[0, rows], k_ref[0, kv], (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32) * scale  # (BQ, BK)
            s = mask(s)
            m_prev = m_ref[rows]                    # (BQ, 128), lanes equal
            m_new = jnp.maximum(m_prev, s.max(axis=-1, keepdims=True))
            alpha = jnp.exp(m_prev - m_new)
            p = jnp.exp(s - m_new[:, :1])
            l_ref[rows] = l_ref[rows] * alpha + p.sum(axis=-1, keepdims=True)
            m_ref[rows] = m_new
            acc_ref[rows] = acc_ref[rows] * alpha[:, :1] + lax.dot_general(
                p.astype(v_ref.dtype), v_ref[0, kv], (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32)

        def whole(s):
            valid = _tile_mask(i * block_q, j * block_k,
                               (block_q, block_k), Sk, Skp, causal, 0)
            return s if valid is None else jnp.where(valid, s, _NEG_INF)

        def diagonal():
            for lo in range(0, block_q, strip):
                valid = _tile_mask(i * block_q + lo, j * block_k + lo,
                                   (strip, strip), Sk, Skp, True, 0)
                step(lambda s: _mask_cols(s, valid, lo),
                     slice(lo, lo + strip))

        if strip:
            if nq > 1:
                pl.when(j < i)(lambda: step(lambda s: s))
            pl.when(j == i)(diagonal)
        elif causal:
            pl.when(j <= last_k(i))(lambda: step(whole))
        else:
            step(whole)

        @pl.when(j == nk - 1)
        def _():
            l = jnp.maximum(l_ref[...], 1e-30)
            o_ref[0] = (acc_ref[...] / l[:, :1]).astype(o_ref.dtype)
            if return_lse:
                lse_ref[0] = m_ref[...] + jnp.log(l)

    q_spec = pl.BlockSpec((1, block_q, D), lambda b, i, j: (b, i, 0))
    out_specs = [q_spec]
    out_shape = [jax.ShapeDtypeStruct((B * H, Sqp, D), q.dtype)]
    if return_lse:
        # lane-replicated rows: a (block_q, 1) column is not a legal tile
        out_specs.append(pl.BlockSpec((1, block_q, _LANES),
                                      lambda b, i, j: (b, i, 0)))
        out_shape.append(
            jax.ShapeDtypeStruct((B * H, Sqp, _LANES), jnp.float32))
    res = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[q_spec, kv_spec, kv_spec],
        out_specs=out_specs,
        out_shape=out_shape,
        scratch_shapes=[pltpu.VMEM((block_q, _LANES), jnp.float32),
                        pltpu.VMEM((block_q, _LANES), jnp.float32),
                        pltpu.VMEM((block_q, D), jnp.float32)],
        compiler_params=_compiler_params(),
        interpret=interpret,
        name="flash_fwd",
    )(_pad_heads(q, Sqp), _pad_heads(k, Skp), _pad_heads(v, Skp))
    out = res[0].reshape(B, H, Sqp, D)[:, :, :Sq]
    if return_lse:
        return out, res[1][:, :, 0].reshape(B, H, Sqp)[:, :, :Sq]
    return out


def _attn_reference(q, k, v, causal, scale):
    """Plain-XLA attention oracle (supports GQA: kv heads dividing q
    heads are broadcast per group)."""
    D = q.shape[-1]
    if scale is None:
        scale = 1.0 / (D ** 0.5)
    if k.shape[1] != q.shape[1]:
        g = q.shape[1] // k.shape[1]
        k = jnp.repeat(k, g, axis=1)
        v = jnp.repeat(v, g, axis=1)
    s = jnp.einsum("bhqd,bhkd->bhqk", q.astype(jnp.float32),
                   k.astype(jnp.float32)) * scale
    if causal:
        Sq, Sk = q.shape[2], k.shape[2]
        mask = lax.broadcasted_iota(jnp.int32, (Sq, Sk), 1) <= \
            lax.broadcasted_iota(jnp.int32, (Sq, Sk), 0)
        s = jnp.where(mask, s, _NEG_INF)
    p = jax.nn.softmax(s, axis=-1)
    return jnp.einsum("bhqk,bhkd->bhqd", p,
                      v.astype(jnp.float32)).astype(q.dtype)


@functools.partial(jax.jit, static_argnames=("causal", "scale", "block_q",
                                             "block_k", "interpret"))
@_x32
def _flash_bwd(q, k, v, out, lse, g, causal=False, scale=None,
               block_q=None, block_k=None, interpret=None):
    """FlashAttention-2 backward, recomputing p = exp(s - lse) blockwise
    from the saved logsumexp — the O(S) memory story of the forward
    carries to the backward (the time-dominant path for long-context
    training).  One kernel, ``flash_bwd_dkv_dq``: a program owns one K/V
    tile, walks the q tiles of its KV head's group, and from each score
    tile feeds all three accumulators — dK and dV its own, dQ a slot of a
    whole-sequence accumulator that stays in VMEM across the k tiles.
    Where that accumulator is over the VMEM count (_geometry), dQ comes
    from ``flash_bwd_dq`` and dK/dV from ``flash_bwd_dkv``: the same
    tiles, the same ``p_and_ds_t``, each rebuilding it.  One tile a
    program as in the forward; the tiles are the backward's own
    (_geometry, ``forward=False``), not the forward's.  Causal with a
    ``diag_strip`` r, the dK/dV walk (merged or not) takes a tile below
    the diagonal with no mask and the diagonal one as b / r strips of
    its transposed tile: strip s, k rows [s r, (s + 1) r), meets query
    columns [s r, b) only, masked on its first r x r block, and feeds
    those rows of dK and dV and those columns' rows of the dQ slot.
    ``flash_bwd_dq`` masks each tile whole as before."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    geo = _geometry(q, k, block_q, block_k, forward=False, causal=causal)
    (B, H, Hk, G, Sq, Sk, D, block_q, block_k, Sqp, Skp, nq,
     nk, *_) = geo
    merged, strip = geo.merged, geo.diag_strip
    dq_grid, dkv_grid = (B * H, nq, nk), (B * Hk, nk, G * nq)
    if merged:
        _say_geometry("flash_bwd_dkv_dq", geo, causal, dkv_grid, strip,
                      merged=True)
    else:
        _say_geometry("flash_bwd_dq", geo, causal, dq_grid, 0, merged=False)
        _say_geometry("flash_bwd_dkv", geo, causal, dkv_grid, strip,
                      merged=False)
    if scale is None:
        scale = 1.0 / (D ** 0.5)
    if interpret is None:
        interpret = _pick_interpret()

    f32 = jnp.float32
    # delta_i = rowsum(dO_i * O_i) (the FA2 `D` term), computed in f32
    delta = jnp.sum(g.astype(f32) * out.astype(f32), axis=-1)  # (B,H,Sq)

    def rows(x, fill):
        # per-row statistics as lane-dense rows, replicated over one
        # sublane tile: (B*H, 8, Sqp) blocks as (1, 8, block_q)
        x = jnp.pad(x.astype(f32), ((0, 0), (0, 0), (0, Sqp - Sq)),
                    constant_values=fill).reshape(B * H, 1, Sqp)
        return jnp.broadcast_to(x, (B * H, _SUBLANES, Sqp))

    qr, gr = _pad_heads(q, Sqp), _pad_heads(g, Sqp)
    kr, vr = _pad_heads(k, Skp), _pad_heads(v, Skp)
    # pad lse with +inf-ish so padded rows give p = exp(-inf) = 0
    lser, deltar = rows(lse, 1e30), rows(delta, 0.0)

    kv_spec, last_k = _kv_spec(pl, D, G, block_q, block_k, causal)

    def first_q(j):
        # causal: q blocks strictly before this k block see nothing
        return (j * block_k) // block_q

    def p_and_ds_t(q_ref, k_ref, v_ref, g_ref, lse_ref, dlt_ref, mask,
                   kr=slice(None), qc=slice(None)):
        """Transposed tiles pT, dsT: (BK, BQ), or of the tile's k rows
        ``kr`` against its query columns ``qc``, the scores through
        ``mask``.  k @ q^T keeps every per-query statistic a (1, BQ) row
        that broadcasts over sublanes — no row->column relayout of
        lse/delta inside the kernel."""
        s_t = lax.dot_general(k_ref[0, kr], q_ref[0, qc],
                              (((1,), (1,)), ((), ())),
                              preferred_element_type=f32) * scale
        s_t = mask(s_t)
        p_t = jnp.exp(s_t - lse_ref[0, :1, qc])
        dp_t = lax.dot_general(v_ref[0, kr], g_ref[0, qc],
                               (((1,), (1,)), ((), ())),
                               preferred_element_type=f32)
        return p_t, p_t * (dp_t - dlt_ref[0, :1, qc]) * scale

    def whole(i, j):
        """The mask of tile (i, j) as it stands: _tile_mask's."""
        def mask(s_t):
            valid = _tile_mask(i * block_q, j * block_k, (block_k, block_q),
                               Sk, Skp, causal, 1)
            return s_t if valid is None else jnp.where(valid, s_t, _NEG_INF)
        return mask

    def dq_of(ds_t, k_ref, kr=slice(None)):
        return lax.dot_general(
            ds_t.astype(k_ref.dtype), k_ref[0, kr],
            (((0,), (0,)), ((), ())), preferred_element_type=f32)

    def dq_alone():
        """dQ from a kernel of its own, on the forward's grid: program
        (b, i) owns one q tile of head b and walks the k tiles."""
        def dq_kernel(q_ref, k_ref, v_ref, g_ref, lse_ref, dlt_ref, dq_ref,
                      acc_ref):
            i, j = pl.program_id(1), pl.program_id(2)

            @pl.when(j == 0)
            def _():
                acc_ref[...] = jnp.zeros(acc_ref.shape, f32)

            def step():
                _, ds_t = p_and_ds_t(q_ref, k_ref, v_ref, g_ref, lse_ref,
                                     dlt_ref, whole(i, j))
                acc_ref[...] += dq_of(ds_t, k_ref)

            if causal:
                pl.when(j <= last_k(i))(step)
            else:
                step()

            @pl.when(j == nk - 1)
            def _():
                dq_ref[0] = acc_ref[...].astype(dq_ref.dtype)

        q_spec = pl.BlockSpec((1, block_q, D), lambda b, i, j: (b, i, 0))
        row_spec = pl.BlockSpec((1, _SUBLANES, block_q),
                                lambda b, i, j: (b, 0, i))
        return pl.pallas_call(
            dq_kernel,
            grid=dq_grid,
            in_specs=[q_spec, kv_spec, kv_spec, q_spec, row_spec, row_spec],
            out_specs=q_spec,
            out_shape=jax.ShapeDtypeStruct((B * H, Sqp, D), q.dtype),
            scratch_shapes=[pltpu.VMEM((block_q, D), f32)],
            compiler_params=_compiler_params(),
            interpret=interpret,
            name="flash_bwd_dq",
        )(qr, kr, vr, gr, lser, deltar)

    # dk/dv: program (b, j) owns one K/V tile of KV head b and walks the
    # G query heads of its group times the q blocks on the last axis, so
    # the GQA reduction over the group happens in the accumulators.
    # Merged, step t of that walk also adds its dQ into slot t of an
    # accumulator that outlives the k tiles (the second axis), and the
    # last k tile casts each slot into the resident dq block, which goes
    # to HBM once a KV head.
    def dkv_kernel(q_ref, k_ref, v_ref, g_ref, lse_ref, dlt_ref,
                   dk_ref, dv_ref, *rest):
        if merged:
            dq_ref, dk_acc, dv_acc, dq_acc = rest
        else:
            dk_acc, dv_acc = rest
        j, t = pl.program_id(1), pl.program_id(2)
        i = t % nq

        @pl.when(t == 0)
        def _():
            dk_acc[...] = jnp.zeros(dk_acc.shape, f32)
            dv_acc[...] = jnp.zeros(dv_acc.shape, f32)

        if merged:
            @pl.when(j == 0)
            def _():
                dq_acc[t] = jnp.zeros(dq_acc.shape[1:], f32)

        def step(mask, kr=slice(None), qc=slice(None)):
            p_t, ds_t = p_and_ds_t(q_ref, k_ref, v_ref, g_ref, lse_ref,
                                   dlt_ref, mask, kr, qc)
            dv_acc[kr] += lax.dot_general(
                p_t.astype(g_ref.dtype), g_ref[0, qc],
                (((1,), (0,)), ((), ())), preferred_element_type=f32)
            dk_acc[kr] += lax.dot_general(
                ds_t.astype(q_ref.dtype), q_ref[0, qc],
                (((1,), (0,)), ((), ())), preferred_element_type=f32)
            if merged:
                dq_acc[t, qc] += dq_of(ds_t, k_ref, kr)

        def diagonal():
            for lo in range(0, block_k, strip):
                valid = _tile_mask(i * block_q + lo, j * block_k + lo,
                                   (strip, strip), Sk, Skp, True, 1)
                step(lambda s_t: _mask_cols(s_t, valid, 0),
                     slice(lo, lo + strip), slice(lo, None))

        if strip:
            if nq > 1:
                pl.when(i > j)(lambda: step(lambda s_t: s_t))
            pl.when(i == j)(diagonal)
        elif causal:
            pl.when(i >= first_q(j))(lambda: step(whole(i, j)))
        else:
            step(whole(i, j))

        @pl.when(t == G * nq - 1)
        def _():
            dk_ref[0] = dk_acc[...].astype(dk_ref.dtype)
            dv_ref[0] = dv_acc[...].astype(dv_ref.dtype)

        if merged:
            @pl.when(j == nk - 1)
            def _():
                dq_ref[0, t] = dq_acc[t].astype(dq_ref.dtype)

    def q_block(j, t):
        i = t % nq
        return jnp.maximum(i, first_q(j)) if causal else i

    dkv_q_spec = pl.BlockSpec(
        (1, block_q, D), lambda b, j, t: (b * G + t // nq, q_block(j, t), 0))
    dkv_row_spec = pl.BlockSpec(
        (1, _SUBLANES, block_q),
        lambda b, j, t: (b * G + t // nq, 0, q_block(j, t)))
    dkv_kv_spec = pl.BlockSpec((1, block_k, D), lambda b, j, t: (b, j, 0))
    out_specs = [dkv_kv_spec, dkv_kv_spec]
    out_shape = [jax.ShapeDtypeStruct((B * Hk, Skp, D), k.dtype),
                 jax.ShapeDtypeStruct((B * Hk, Skp, D), v.dtype)]
    scratch_shapes = [pltpu.VMEM((block_k, D), f32),
                      pltpu.VMEM((block_k, D), f32)]
    if merged:
        # dq of KV head b's group, tile by tile in the walk's order: the
        # (B*H, Sqp, D) array seen as (B*Hk, G*nq, block_q, D)
        out_specs.append(pl.BlockSpec((1, G * nq, block_q, D),
                                      lambda b, j, t: (b, 0, 0, 0)))
        out_shape.append(
            jax.ShapeDtypeStruct((B * Hk, G * nq, block_q, D), q.dtype))
        scratch_shapes.append(pltpu.VMEM((G * nq, block_q, D), f32))
    dk, dv, *dq = pl.pallas_call(
        dkv_kernel,
        grid=dkv_grid,
        in_specs=[dkv_q_spec, dkv_kv_spec, dkv_kv_spec, dkv_q_spec,
                  dkv_row_spec, dkv_row_spec],
        out_specs=out_specs,
        out_shape=out_shape,
        scratch_shapes=scratch_shapes,
        compiler_params=_compiler_params(
            "arbitrary" if merged else "parallel"),
        interpret=interpret,
        name="flash_bwd_dkv_dq" if merged else "flash_bwd_dkv",
    )(qr, kr, vr, gr, lser, deltar)
    dq = dq[0] if merged else dq_alone()

    dq = dq.reshape(B, H, Sqp, D)[:, :, :Sq]
    dk = dk.reshape(B, Hk, Skp, D)[:, :, :Sk]
    dv = dv.reshape(B, Hk, Skp, D)[:, :, :Sk]
    return dq, dk, dv


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6))
def flash_attention(q, k, v, causal=False, scale=None,
                    block_q=None, block_k=None):
    """Blocked online-softmax attention.  q: (B, H, S, D); k/v:
    (B, Hk, S, D) with Hk dividing H — Hk < H is grouped-query /
    multi-query attention with the shared KV never materialized.

    The rows of a score tile are derived from the shapes, by the forward
    and by the backward each for itself (_geometry).  block_q/block_k
    force them instead, for both (multiples of 128 that fit the VMEM
    count, else ValueError): for tests that want several tiles at a small
    S."""
    return _flash_fwd(q, k, v, causal=causal, scale=scale,
                      block_q=block_q, block_k=block_k)


def _fa_fwd(q, k, v, causal, scale, block_q, block_k):
    out, lse = _flash_fwd(q, k, v, causal=causal, scale=scale,
                          block_q=block_q, block_k=block_k,
                          return_lse=True)
    # what a rematerialised loop body keeps of this op: named here, inside
    # the rule, so that the forward kernel is not run again for either
    out = base.tag_for_remat(out, "attn_out")
    lse = base.tag_for_remat(lse, "attn_lse")
    return out, (q, k, v, out, lse)


def _fa_bwd(causal, scale, block_q, block_k, res, g):
    q, k, v, out, lse = res
    return _flash_bwd(q, k, v, out, lse, g, causal=causal, scale=scale,
                      block_q=block_q, block_k=block_k)


flash_attention.defvjp(_fa_fwd, _fa_bwd)


@register("_contrib_FlashAttention",
          arg_names=["query", "key", "value"],
          attr_defaults={"causal": False, "scale": None},
          aliases=("flash_attention", "_contrib_flash_attention"))
def _flash_attention_op(query, key, value, causal=False, scale=None, **kw):
    """Registry entry point: usable from mx.nd / mx.sym / gluon.  Always
    the Pallas flash kernels, their tiles derived from the shapes."""
    return flash_attention(query, key, value, bool(causal), scale)


def gqa_repeat_kv(q, k, v):
    """Validate GQA head counts and materialize KV at full head count.

    The flash kernel shares KV without this; sequence-parallel paths call
    it only when their collective layout cannot keep the compact form.
    """
    H, Hk = q.shape[1], k.shape[1]
    if Hk == H:
        return k, v
    if H % Hk:
        raise ValueError(f"q heads {H} not divisible by kv heads {Hk}")
    g = H // Hk
    return jnp.repeat(k, g, axis=1), jnp.repeat(v, g, axis=1)
