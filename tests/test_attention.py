"""Flash/ring attention tests (new TPU-native capability, SURVEY.md §5.7).

The Pallas kernels run in interpret mode on the CPU mesh: the same traced
program the chip compiles (same padding, tiles and index maps), executed
by the Pallas interpreter instead of Mosaic.  The tiles are derived from
the shapes, so a small S is one tile: the tests that want several tiles,
block skipping and clamped index maps force 128-row tiles on purpose.
What the interpreter cannot show — that Mosaic accepts the program — is
covered by the cross-lowering tests at the bottom and by chip_smoke.py on
the chip."""
import numpy as np
import pytest
import jax
import jax.numpy as jnp

import mxnet_tpu as mx
from mxnet_tpu import parallel as par
from mxnet_tpu.ops.attention import flash_attention, _attn_reference


def _rand_qkv(B=2, H=2, S=96, D=32, seed=0):
    rng = np.random.RandomState(seed)
    mk = lambda: jnp.asarray(rng.randn(B, H, S, D).astype('float32'))
    return mk(), mk(), mk()


@pytest.mark.parametrize('causal', [False, True])
def test_flash_matches_reference(causal):
    q, k, v = _rand_qkv()
    out = flash_attention(q, k, v, causal, None)
    ref = _attn_reference(q, k, v, causal, None)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=1e-5, atol=1e-5)


def test_flash_unaligned_seq():
    """Sequence not a multiple of the block size exercises the padding
    masks."""
    q, k, v = _rand_qkv(S=100)
    out = flash_attention(q, k, v, True, None)
    ref = _attn_reference(q, k, v, True, None)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=1e-5, atol=1e-5)


def test_flash_cross_attention():
    rng = np.random.RandomState(1)
    q = jnp.asarray(rng.randn(1, 2, 48, 16).astype('float32'))
    k = jnp.asarray(rng.randn(1, 2, 80, 16).astype('float32'))
    v = jnp.asarray(rng.randn(1, 2, 80, 16).astype('float32'))
    out = flash_attention(q, k, v, False, None)
    ref = _attn_reference(q, k, v, False, None)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=1e-5, atol=1e-5)


def test_flash_gradients():
    q, k, v = _rand_qkv(S=64)
    f = lambda *xs: jnp.sum(flash_attention(*xs, True, None) ** 2)
    fr = lambda *xs: jnp.sum(_attn_reference(*xs, True, None) ** 2)
    g = jax.grad(f, argnums=(0, 1, 2))(q, k, v)
    gr = jax.grad(fr, argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(g, gr):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=1e-4, atol=1e-4)


def test_flash_via_op_registry():
    """The op is reachable from the nd/sym frontends."""
    q, k, v = _rand_qkv(S=32, D=16)
    out = mx.nd.flash_attention(mx.nd.NDArray(q), mx.nd.NDArray(k),
                                mx.nd.NDArray(v), causal=True)
    ref = _attn_reference(q, k, v, True, None)
    np.testing.assert_allclose(out.asnumpy(), np.asarray(ref),
                               rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize('causal', [False, True])
def test_ring_attention_exact(causal):
    mesh = par.make_mesh(dp=1, sp=8)
    q, k, v = _rand_qkv(S=64)
    qs, ks, vs = (par.shard_seq(x, mesh) for x in (q, k, v))
    out = par.ring_attention(qs, ks, vs, mesh, causal=causal)
    ref = _attn_reference(q, k, v, causal, None)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=1e-5, atol=1e-5)


def test_ring_attention_grad():
    mesh = par.make_mesh(dp=1, sp=8)
    q, k, v = _rand_qkv(S=64)
    qs, ks, vs = (par.shard_seq(x, mesh) for x in (q, k, v))
    f = lambda a, b, c: jnp.sum(
        par.ring_attention(a, b, c, mesh, causal=True) ** 2)
    fr = lambda a, b, c: jnp.sum(_attn_reference(a, b, c, True, None) ** 2)
    g = jax.grad(f, argnums=(0, 1, 2))(qs, ks, vs)
    gr = jax.grad(fr, argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(g, gr):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=1e-4, atol=1e-4)


def test_ring_attention_dp_sp():
    """dp and sp compose: batch over dp, sequence over the sp ring."""
    mesh = par.make_mesh(dp=2, sp=4)
    q, k, v = _rand_qkv(B=4, S=32)
    from jax.sharding import NamedSharding, PartitionSpec as P
    sh = NamedSharding(mesh, P('dp', None, 'sp', None))
    qs, ks, vs = (jax.device_put(x, sh) for x in (q, k, v))
    out = par.ring_attention(qs, ks, vs, mesh, causal=True)
    ref = _attn_reference(q, k, v, True, None)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize('causal', [False, True])
def test_flash_backward_kernel_matches_reference(causal):
    """The Pallas dq/dk/dv kernels must match jax.vjp of plain-XLA
    attention (FA2 backward correctness)."""
    import jax
    import jax.numpy as jnp
    from mxnet_tpu.ops import attention as A
    rng = np.random.RandomState(0)
    B, H, Sq, D = 2, 3, 80, 16   # non-multiple of block sizes
    q = jnp.asarray(rng.randn(B, H, Sq, D).astype('f'))
    k = jnp.asarray(rng.randn(B, H, Sq, D).astype('f'))
    v = jnp.asarray(rng.randn(B, H, Sq, D).astype('f'))
    g = jnp.asarray(rng.randn(B, H, Sq, D).astype('f'))

    out, vjp = jax.vjp(
        lambda q_, k_, v_: A._attn_reference(q_, k_, v_, causal, None),
        q, k, v)
    dq_ref, dk_ref, dv_ref = vjp(g)

    dq, dk, dv = jax.vjp(
        lambda q_, k_, v_: A.flash_attention(q_, k_, v_, causal, None),
        q, k, v)[1](g)
    np.testing.assert_allclose(np.asarray(dq), np.asarray(dq_ref),
                               rtol=2e-3, atol=2e-3)
    np.testing.assert_allclose(np.asarray(dk), np.asarray(dk_ref),
                               rtol=2e-3, atol=2e-3)
    np.testing.assert_allclose(np.asarray(dv), np.asarray(dv_ref),
                               rtol=2e-3, atol=2e-3)


def test_flash_backward_multi_block():
    """Several q and k blocks (3x3 tiles of 128 after padding 300) with
    causal masking and a GQA group: block skipping, the clamped K/V and q
    index maps and the in-kernel group reduction all run."""
    import jax
    import jax.numpy as jnp
    from mxnet_tpu.ops import attention as A
    rng = np.random.RandomState(3)
    q = jnp.asarray(rng.randn(1, 2, 300, 8).astype('f'))
    k = jnp.asarray(rng.randn(1, 1, 300, 8).astype('f'))
    v = jnp.asarray(rng.randn(1, 1, 300, 8).astype('f'))
    g = jnp.asarray(rng.randn(1, 2, 300, 8).astype('f'))
    ref = jax.vjp(lambda a, b, c: A._attn_reference(a, b, c, True, None),
                  q, k, v)[1](g)
    got = A._flash_bwd(q, k, v,
                       *A._flash_fwd(q, k, v, causal=True,
                                     return_lse=True),
                       g, causal=True, block_q=128, block_k=128)
    for x, y in zip(got, ref):
        np.testing.assert_allclose(np.asarray(x), np.asarray(y),
                                   rtol=2e-3, atol=2e-3)


# (Sq, Sk, H, Hk, causal, block_q, block_k): several tiles each way at
# forced 128-row and rectangular tiles — GQA groups of 2 and 1, Sq != Sk,
# K padding (Sk 300 in tiles of 128 / 256), causal skipping with
# block_q != block_k (the clamped K/V and q index maps); square causal
# tiles run their diagonal in strips (one of 128 at 128, two of 256 at 512)
_FORCED_TILES = [
    (1024, 1024, 2, 1, True, 512, 512),
    (300, 300, 2, 2, True, 128, 128),
    (300, 300, 2, 1, False, 128, 128),
    (384, 384, 2, 1, True, 256, 128),
    (384, 384, 2, 2, False, 256, 128),
    (300, 300, 2, 1, True, 128, 256),
    (384, 384, 4, 2, True, 128, 256),
    (300, 384, 2, 1, False, 128, 128),
    (384, 300, 2, 2, False, 256, 128),
    (200, 300, 2, 1, False, 128, 256),
    (384, 300, 2, 1, True, 128, 128),
]


def _forced_inputs(Sq, Sk, H, Hk):
    rng = np.random.RandomState(Sq + Sk + H + Hk)
    mk = lambda h, s: jnp.asarray(rng.randn(1, h, s, 8).astype('f'))
    return mk(H, Sq), mk(Hk, Sk), mk(Hk, Sk), mk(H, Sq)


def _ref_lse(q, k, causal):
    g = q.shape[1] // k.shape[1]
    s = jnp.einsum("bhqd,bhkd->bhqk", q, jnp.repeat(k, g, axis=1)) \
        / (q.shape[-1] ** 0.5)
    if causal:
        Sq, Sk = s.shape[-2:]
        s = jnp.where(jnp.arange(Sk)[None, :] <= jnp.arange(Sq)[:, None],
                      s, -jnp.inf)
    return jax.scipy.special.logsumexp(s, axis=-1)


@pytest.mark.parametrize("Sq,Sk,H,Hk,causal,bq,bk", _FORCED_TILES)
def test_flash_forward_lse_forced_tiles(Sq, Sk, H, Hk, causal, bq, bk):
    from mxnet_tpu.ops import attention as A
    q, k, v, _ = _forced_inputs(Sq, Sk, H, Hk)
    geo = A._geometry(q, k, bq, bk, forward=True)
    assert (geo.block_q, geo.block_k, geo.derived) == (bq, bk, False)
    assert geo.nq > 1 and geo.nk > 1
    out, lse = A._flash_fwd(q, k, v, causal=causal, return_lse=True,
                            block_q=bq, block_k=bk)
    np.testing.assert_allclose(
        np.asarray(out), np.asarray(_attn_reference(q, k, v, causal, None)),
        rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(np.asarray(lse),
                               np.asarray(_ref_lse(q, k, causal)),
                               rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("Sq,Sk,H,Hk,causal,bq,bk", _FORCED_TILES)
def test_flash_backward_forced_tiles(Sq, Sk, H, Hk, causal, bq, bk):
    """dq, dk, dv from ``flash_bwd_dkv_dq`` (the group reduced in its
    dk/dv accumulators, dq carried across the k tiles), through the
    public function so the explicit blocks reach the backward too."""
    q, k, v, g = _forced_inputs(Sq, Sk, H, Hk)
    ref = jax.vjp(lambda a, b, c: _attn_reference(a, b, c, causal, None),
                  q, k, v)[1](g)
    got = jax.vjp(lambda a, b, c: flash_attention(a, b, c, causal, None,
                                                  bq, bk), q, k, v)[1](g)
    for x, y in zip(got, ref):
        assert x.shape == y.shape
        np.testing.assert_allclose(np.asarray(x), np.asarray(y),
                                   rtol=2e-3, atol=2e-3)


def _bwd_uncached(monkeypatch, merged, q, k, v, g, causal, bq, bk):
    """``_flash_bwd`` outside jit's cache, as the merged kernel or (the
    budget lowered to just under the merged count, which is what a long
    sequence does) as the dQ and dK/dV kernels apart."""
    from mxnet_tpu.ops import attention as A
    out, lse = A._flash_fwd(q, k, v, causal=causal, return_lse=True,
                            block_q=bq, block_k=bk)
    geo = A._geometry(q, k, bq, bk, forward=False)
    assert geo.merged
    if not merged:
        monkeypatch.setattr(A, "_VMEM_BUDGET", geo.vmem_bytes - 1)
        two = A._geometry(q, k, bq, bk, forward=False)
        assert not two.merged and two.vmem_bytes < geo.vmem_bytes
        assert (two.block_q, two.block_k) == (geo.block_q, geo.block_k)
    return A._flash_bwd.__wrapped__(q, k, v, out, lse, g, causal=causal,
                                    block_q=bq, block_k=bk)


@pytest.mark.parametrize("Sq,Sk,H,Hk,causal,bq,bk", _FORCED_TILES + [
    (100, 100, 4, 2, True, None, None),      # one derived tile
    (1100, 600, 2, 1, False, None, None),    # 3 x 384 by 2 x 384 derived
])
def test_flash_backward_merged_equals_two_kernels(monkeypatch, Sq, Sk, H,
                                                  Hk, causal, bq, bk):
    """One pass over the score tiles gives what two gave: the same p and
    ds feed the same sums in the same order, so dk and dv are the dK/dV
    kernel's bit for bit and dq the dQ kernel's to rounding."""
    q, k, v, g = _forced_inputs(Sq, Sk, H, Hk)
    one = _bwd_uncached(monkeypatch, True, q, k, v, g, causal, bq, bk)
    two = _bwd_uncached(monkeypatch, False, q, k, v, g, causal, bq, bk)
    ref = jax.vjp(lambda a, b, c: _attn_reference(a, b, c, causal, None),
                  q, k, v)[1](g)
    for x, y, r in zip(one, two, ref):
        assert x.shape == y.shape == r.shape
        np.testing.assert_allclose(np.asarray(x), np.asarray(y),
                                   rtol=1e-6, atol=1e-6)
        np.testing.assert_allclose(np.asarray(x), np.asarray(r),
                                   rtol=2e-3, atol=2e-3)
    np.testing.assert_array_equal(np.asarray(one[1]), np.asarray(two[1]))
    np.testing.assert_array_equal(np.asarray(one[2]), np.asarray(two[2]))


def test_flash_backward_merged_equals_two_kernels_bf16(monkeypatch):
    """bf16 operands, head 64, a group of 2, 3 x 3 causal tiles: each
    kernel rounds ds to bf16 once, before the same matmuls."""
    rng = np.random.RandomState(5)
    mk = lambda h: jnp.asarray(rng.randn(1, h, 384, 64), jnp.bfloat16)
    q, k, v, g = mk(4), mk(2), mk(2), mk(4)
    one = _bwd_uncached(monkeypatch, True, q, k, v, g, True, 128, 128)
    two = _bwd_uncached(monkeypatch, False, q, k, v, g, True, 128, 128)
    for x, y in zip(one, two):
        assert x.dtype == jnp.bfloat16
        np.testing.assert_array_equal(np.asarray(x, np.float32),
                                      np.asarray(y, np.float32))


# --- the causal diagonal in strips (ops/attention.py _DIAG_STRIP) ----------
# (S, H, Hk, dtype, block): forced 256-row tiles in strips of 128 — two
# a diagonal tile, tiles below it unmasked — at S 512 / 1024, GQA 4/1 and
# 8/2, K padding (S 1100: 5 tiles of 256, the last one padded); derived,
# a sequence of one tile, with no tile below the diagonal (S 384: three
# strips; S 200: two, in a tile padded to 256)
_STRIPS = [
    (512, 2, 2, jnp.float32, 256),
    (1024, 2, 2, jnp.float32, 256),
    (512, 4, 1, jnp.float32, 256),
    (512, 8, 2, jnp.float32, 256),
    (1100, 2, 1, jnp.float32, 256),
    (512, 2, 2, jnp.bfloat16, 256),
    (1024, 4, 1, jnp.bfloat16, 256),
    (1100, 8, 2, jnp.bfloat16, 256),
    (384, 2, 1, jnp.float32, None),
    (200, 4, 1, jnp.bfloat16, None),
]
# against the reference: the forced-tile tolerances in float32; in bf16
# the outputs' own rounding.  Against the masked tile: the same sums
# with exact zeros left out, to the last bit but for their order
_TOL = {jnp.float32: (1e-5, 2e-3, 1e-6), jnp.bfloat16: (2e-2, 6e-2, 1e-2)}


def _strip_inputs(S, H, Hk, dtype):
    rng = np.random.RandomState(S + 10 * H + Hk)
    mk = lambda h: jnp.asarray(rng.randn(1, h, S, 16), dtype)
    return mk(H), mk(Hk), mk(Hk), mk(H)


def _striped_and_masked(monkeypatch, fn, q, k, block):
    """``fn()`` with the diagonal in strips of 128, and again with the
    strips off: the masked tile the parent ran."""
    from mxnet_tpu.ops import attention as A
    monkeypatch.setattr(A, "_DIAG_STRIP",
                        {"fwd": 128, "fwd_wide": 128, "bwd": 128})
    for forward in (True, False):
        geo = A._geometry(q, k, block, block, forward, causal=True)
        assert geo.diag_strip == 128 and (geo.nq > 1) == (block == 256)
    striped = fn()
    monkeypatch.setattr(A, "_DIAG_STRIP", dict.fromkeys(A._DIAG_STRIP, 0))
    assert A._geometry(q, k, block, block, True, causal=True).diag_strip == 0
    return striped, fn()


def _close(x, y, tol):
    np.testing.assert_allclose(np.asarray(x, np.float32),
                               np.asarray(y, np.float32), rtol=tol, atol=tol)


@pytest.mark.parametrize("S,H,Hk,dtype,block", _STRIPS)
def test_flash_forward_diagonal_strips(monkeypatch, S, H, Hk, dtype, block):
    """out and lse of the strips against the reference and against the
    masked tile (outside jit's cache, so each traces its own program)."""
    from mxnet_tpu.ops import attention as A
    q, k, v, _ = _strip_inputs(S, H, Hk, dtype)
    (out, lse), (out0, lse0) = _striped_and_masked(
        monkeypatch, lambda: A._flash_fwd.__wrapped__(
            q, k, v, causal=True, return_lse=True, block_q=block,
            block_k=block), q, k, block)
    ref_tol, _, same_tol = _TOL[dtype]
    assert out.dtype == dtype and out.shape == q.shape
    _close(out, _attn_reference(q, k, v, True, None), ref_tol)
    _close(lse, _ref_lse(q.astype(jnp.float32), k.astype(jnp.float32), True),
           1e-5 if dtype == jnp.float32 else 1e-2)
    _close(out, out0, same_tol)
    _close(lse, lse0, 1e-6)


@pytest.mark.parametrize("S,H,Hk,dtype,block", _STRIPS)
def test_flash_backward_diagonal_strips(monkeypatch, S, H, Hk, dtype, block):
    """dq, dk, dv of the merged kernel in strips against jax.vjp of the
    reference and against the masked tile, from the same forward."""
    from mxnet_tpu.ops import attention as A
    q, k, v, g = _strip_inputs(S, H, Hk, dtype)
    out, lse = A._flash_fwd(q, k, v, causal=True, return_lse=True)
    got, masked = _striped_and_masked(
        monkeypatch, lambda: A._flash_bwd.__wrapped__(
            q, k, v, out, lse, g, causal=True, block_q=block,
            block_k=block), q, k, block)
    ref = jax.vjp(lambda a, b, c: _attn_reference(a, b, c, True, None),
                  q, k, v)[1](g)
    _, grad_tol, same_tol = _TOL[dtype]
    for x, y, r in zip(got, masked, ref):
        assert x.shape == r.shape and x.dtype == dtype
        _close(x, r, grad_tol)
        _close(x, y, same_tol)


@pytest.mark.parametrize("Sq,Sk,causal,D,dtype,blocks,fwd,bwd", [
    # (Sq, Sk, causal, D, dtype, blocks): the strips' rows (forward,
    # backward) and the live tiles of a head (full, diagonal, masked);
    # the forward's tile over 512 rows strips only a sequence of more
    # than one tile
    (1024, 1024, True, 64, jnp.bfloat16, (None, None),
     (0, 0, 0, 1), (128, 1, 2, 0)),                         # gpt2m
    (4096, 4096, True, 128, jnp.bfloat16, (None, None),
     (512, 6, 4, 0), (128, 28, 8, 0)),                      # ouro
    (2048, 2048, True, 64, jnp.bfloat16, (None, None),
     (512, 1, 2, 0), (128, 6, 4, 0)),
    (1100, 1100, True, 64, jnp.bfloat16, (None, None),
     (0, 0, 0, 3), (128, 3, 3, 0)),          # 640 rows: no strips of 512
    (512, 512, True, 64, jnp.bfloat16, (None, None),
     (128, 0, 1, 0), (128, 0, 1, 0)),        # one tile, four strips
    (100, 100, True, 64, jnp.bfloat16, (None, None),
     (128, 0, 1, 0), (128, 0, 1, 0)),        # one strip: the tile masked
    (384, 384, True, 64, jnp.float32, (128, 128),
     (128, 3, 3, 0), (128, 3, 3, 0)),
    # today's tiles: not causal, Sq != Sk, tiles not square
    (1024, 1024, False, 64, jnp.bfloat16, (None, None),
     (0, 0, 0, 0), (0, 0, 0, 0)),
    (1100, 1100, False, 64, jnp.bfloat16, (None, None),
     (0, 0, 0, 2), (0, 0, 0, 3)),            # K padding: the last k tiles
    (1024, 2048, True, 64, jnp.bfloat16, (None, None),
     (0, 0, 0, 1), (0, 0, 0, 3)),
    (384, 384, True, 64, jnp.float32, (256, 128),
     (0, 0, 0, 5), (0, 0, 0, 5)),
    (4096, 4096, True, 128, jnp.float32, (None, None),
     (0, 0, 0, 20), (128, 28, 8, 0)),        # forward 1024 x 512
])
def test_geometry_strips_only_a_square_causal_diagonal(Sq, Sk, causal, D,
                                                       dtype, blocks, fwd,
                                                       bwd):
    """The path is chosen by causal, Sq == Sk and square tiles alone (a
    forward tile over 512 rows by more than one of them); the strips are
    _DIAG_STRIP's rows where they divide the tile, and the tile kinds
    count one head's live tiles."""
    from mxnet_tpu.ops import attention as A
    q = jax.ShapeDtypeStruct((1, 4, Sq, D), dtype)
    k = jax.ShapeDtypeStruct((1, 2, Sk, D), dtype)
    for forward, want in ((True, fwd), (False, bwd)):
        geo = A._geometry(q, k, *blocks, forward, causal=causal)
        kinds = A._tile_kinds(geo, causal, geo.diag_strip)
        assert (geo.diag_strip, kinds["tiles_full"], kinds["tiles_diag"],
                kinds["tiles_masked"]) == want
        if geo.diag_strip:
            assert geo.block_q == geo.block_k
            assert geo.block_q % geo.diag_strip == 0
            assert geo.diag_strip % A._LANES == 0
        # the strips change no tile and no count
        assert geo[:-1] == A._geometry(q, k, *blocks, forward)[:-1]


def test_flash_blocks_must_be_lane_multiples():
    q, k, v = _rand_qkv(S=64)
    with pytest.raises(ValueError, match="multiples of 128"):
        flash_attention(q, k, v, True, None, 32, 32)


# --- the sizing function alone (ops/attention.py _geometry) -----------------

def _sized(S, D, dtype, forward, block_q=None, block_k=None, Sk=None):
    from mxnet_tpu.ops import attention as A
    q = jax.ShapeDtypeStruct((1, 4, S, D), dtype)
    k = jax.ShapeDtypeStruct((1, 2, Sk or S, D), dtype)
    return A._geometry(q, k, block_q, block_k, forward)


@pytest.mark.parametrize("S,D,dtype,forward,blocks,tiles", [
    # the ladder's shape: the two preferred tiles
    (1024, 64, jnp.bfloat16, True, (1024, 1024), (1, 1)),
    (1024, 64, jnp.bfloat16, False, (512, 512), (2, 2)),
    # the fewest EQUAL lane-multiple tiles, not 3 x 512 / 2 x 1024
    (1100, 64, jnp.bfloat16, False, (384, 384), (3, 3)),
    (1100, 64, jnp.bfloat16, True, (640, 640), (2, 2)),
    # a short sequence is one tile of its own padded length
    (100, 64, jnp.bfloat16, True, (128, 128), (1, 1)),
    (100, 64, jnp.bfloat16, False, (128, 128), (1, 1)),
    (577, 64, jnp.bfloat16, True, (640, 640), (1, 1)),
    (577, 64, jnp.bfloat16, False, (384, 384), (2, 2)),
    # long sequences keep the preferred tiles
    (4096, 64, jnp.bfloat16, True, (1024, 1024), (4, 4)),
    (8192, 128, jnp.bfloat16, False, (512, 512), (16, 16)),
    # float32, head 128: the forward's 1024 x 1024 counts 14.5 MiB, so
    # its wider side (k on a tie) is halved; the backward's tile fits
    (4096, 128, jnp.float32, True, (1024, 512), (4, 8)),
    (4096, 128, jnp.float32, False, (512, 512), (8, 8)),
])
def test_geometry_derives_tiles_from_the_shape(S, D, dtype, forward, blocks,
                                               tiles):
    from mxnet_tpu.ops import attention as A
    geo = _sized(S, D, dtype, forward)
    assert (geo.block_q, geo.block_k) == blocks
    assert (geo.nq, geo.nk) == tiles
    assert (geo.Sqp, geo.Skp) == (blocks[0] * tiles[0], blocks[1] * tiles[1])
    assert geo.Sqp - S < A._LANES * geo.nq     # under a lane tile a block
    assert geo.derived and geo.G == 2
    # a merged backward counts its resident dQ (here G 2: all but the two
    # whose dQ rows are 4 MB — S 8192 at D 128, S 4096 at D 128 float32)
    assert geo.merged == (not forward and S * D < 4096 * 128)
    assert geo.vmem_bytes == A._vmem_bytes(
        *blocks, D, jnp.dtype(dtype).itemsize, forward,
        geo.G * geo.Sqp if geo.merged else 0)
    assert geo.vmem_bytes <= A._VMEM_BUDGET


@pytest.mark.parametrize("S,D,dtype,H,Hk,blocks,tiles,merged", [
    # the cell's shape and the ladder's: dQ of a group stays in VMEM
    (1024, 64, jnp.bfloat16, 16, 16, (None, None), (512, 512), True),
    (1024, 128, jnp.bfloat16, 8, 2, (None, None), (512, 512), True),
    (4096, 64, jnp.bfloat16, 16, 16, (None, None), (512, 512), True),
    (8192, 64, jnp.bfloat16, 16, 16, (None, None), (512, 512), True),
    (4096, 128, jnp.float32, 8, 8, (None, None), (512, 512), True),
    (577, 64, jnp.bfloat16, 16, 16, (None, None), (384, 384), True),
    (300, 8, jnp.float32, 2, 1, (128, 128), (128, 128), True),
    # the accumulator is over the count: 4 x 8192 rows of 128 (33 MB), a
    # group of 4 at S 4096 (16 MB), 16384 rows alone (19 MB)
    (8192, 128, jnp.bfloat16, 4, 1, (None, None), (512, 512), False),
    (4096, 64, jnp.bfloat16, 4, 1, (None, None), (512, 512), False),
    (16384, 64, jnp.bfloat16, 8, 8, (None, None), (512, 512), False),
    (8192, 128, jnp.bfloat16, 4, 1, (256, 512), (256, 512), False),
])
def test_geometry_merges_the_backward_where_dq_fits(S, D, dtype, H, Hk,
                                                    blocks, tiles, merged):
    """One algorithm, a parameter read off the shape: merged where the
    whole-sequence dQ accumulator fits the count with the tiles, else the
    SAME tiles for the two kernels — falling back never narrows a tile
    and never raises."""
    from mxnet_tpu.ops import attention as A
    q = jax.ShapeDtypeStruct((1, H, S, D), dtype)
    k = jax.ShapeDtypeStruct((1, Hk, S, D), dtype)
    geo = A._geometry(q, k, *blocks, forward=False)
    assert (geo.block_q, geo.block_k) == tiles
    item = jnp.dtype(dtype).itemsize
    with_dq = A._vmem_bytes(*tiles, D, item, False, geo.G * geo.Sqp)
    apart = A._vmem_bytes(*tiles, D, item, False)
    assert with_dq > apart
    assert geo.merged == merged == (with_dq <= A._VMEM_BUDGET)
    assert geo.vmem_bytes == (with_dq if merged else apart)
    assert geo.vmem_bytes <= A._VMEM_BUDGET
    assert not A._geometry(q, k, *blocks, forward=True).merged


def test_geometry_sides_are_sized_apart():
    """Sq != Sk: each side from its own length."""
    geo = _sized(200, 64, jnp.bfloat16, True, Sk=3000)
    assert (geo.block_q, geo.block_k, geo.nq, geo.nk) == (256, 1024, 1, 3)
    geo = _sized(200, 64, jnp.bfloat16, False, Sk=3000)
    assert (geo.block_q, geo.block_k, geo.nq, geo.nk) == (256, 512, 1, 6)


@pytest.mark.parametrize("forward", [True, False])
def test_geometry_explicit_blocks_are_taken_as_given(forward):
    geo = _sized(1024, 64, jnp.bfloat16, forward, 256, 128)
    assert (geo.block_q, geo.block_k, geo.nq, geo.nk) == (256, 128, 4, 8)
    assert not geo.derived
    # one side explicit: the other is derived, and nothing is narrowed
    geo = _sized(1024, 64, jnp.bfloat16, forward, None, 128)
    assert geo.block_q == (1024 if forward else 512) and geo.block_k == 128
    assert not geo.derived
    # larger than the sequence: one tile of the padded length
    geo = _sized(300, 64, jnp.bfloat16, forward, 512, 512)
    assert (geo.block_q, geo.block_k, geo.Sqp) == (384, 384, 384)


@pytest.mark.parametrize("forward,dtype,D,blocks", [
    (True, jnp.bfloat16, 64, (1024, 2048)),    # PR 26's ladder: no result
    (False, jnp.bfloat16, 64, (1024, 2048)),
    (True, jnp.float32, 128, (1024, 1024)),
    (False, jnp.float32, 256, (2048, 1024)),
])
def test_geometry_refuses_an_explicit_tile_over_the_vmem_count(
        forward, dtype, D, blocks):
    from mxnet_tpu.ops import attention as A
    with pytest.raises(ValueError, match="bytes of VMEM") as e:
        _sized(4096, D, dtype, forward, *blocks)
    count = A._vmem_bytes(*blocks, D, jnp.dtype(dtype).itemsize, forward)
    assert count > A._VMEM_BUDGET
    assert str(count) in str(e.value) and str(A._VMEM_BUDGET) in str(e.value)


def test_explicit_tile_over_the_count_raises_from_the_public_call():
    q = jnp.zeros((1, 1, 4096, 64), jnp.bfloat16)
    with pytest.raises(ValueError, match="bytes of VMEM"):
        flash_attention(q, q, q, True, None, 1024, 2048)


def test_geometry_instant_once_a_compile_never_per_call(monkeypatch):
    """Each kernel says what engaged in one ``mx.attention.geometry``
    instant while its caller is traced: the forward and the backward their
    own tiles, nothing on a call that hits jit's cache, nothing when
    tracing is off."""
    from mxnet_tpu import tracing
    from mxnet_tpu.ops import attention as A

    def said():
        return [r["args"] for r in tracing.ring_records()
                if r["name"] == "mx.attention.geometry"]

    # a shape no other test of this file uses: jit's cache is the process's
    q = jnp.ones((1, 4, 200, 24), jnp.float32)
    k = v = jnp.ones((1, 2, 136, 24), jnp.float32)
    monkeypatch.setenv("MXNET_TRACE", "1")
    tracing.reconfigure()
    try:
        tracing.reset()
        flash_attention(q, k, v, False, None)
        (fwd,) = said()
        assert fwd == {
            "kernel": "flash_fwd", "Sq": 200, "Sk": 136, "D": 24, "G": 2,
            "causal": False, "block_q": 256, "block_k": 256,
            "grid": [4, 1, 1], "grid_steps": 4, "derived": True,
            "vmem_bytes": fwd["vmem_bytes"], "diag_strip": 0,
            "tiles_full": 0, "tiles_diag": 0, "tiles_masked": 1}
        assert 0 < fwd["vmem_bytes"] < 14 << 20
        for _ in range(3):
            flash_attention(q, k, v, False, None)
        assert len(said()) == 1
        # the training path: the forward that keeps lse is another
        # program, and the backward's one kernel speaks for itself
        f = jax.jit(jax.grad(
            lambda a, b, c: jnp.sum(flash_attention(a, b, c, False, None,
                                                    128, 128)),
            argnums=(0, 1, 2)))
        f(q, k, v)
        got = said()[1:]
        assert [a["kernel"] for a in got] == ["flash_fwd", "flash_bwd_dkv_dq"]
        assert all((a["block_q"], a["block_k"], a["derived"])
                   == (128, 128, False) for a in got)
        assert [a["grid"] for a in got] == [[4, 2, 2], [2, 2, 4]]
        # only the backward says whether it merged
        assert [a.get("merged") for a in got] == [None, True]
        assert got[1]["vmem_bytes"] == A._vmem_bytes(128, 128, 24, 4, False,
                                                     2 * 256)
        for _ in range(3):
            f(q, k, v)
        assert len(said()) == 3
        # dQ's accumulator over the count (here: the budget under it):
        # the two kernels, each with its own grid, once a compile too
        monkeypatch.setattr(A, "_VMEM_BUDGET", got[1]["vmem_bytes"] - 1)
        f2 = jax.jit(jax.grad(
            lambda a, b, c: jnp.sum(flash_attention(a, b, c, True, None,
                                                    128, 128)),
            argnums=(0, 1, 2)))
        for _ in range(2):
            f2(q, k, v)
        two = said()[4:]
        assert [(a["kernel"], a["merged"], a["grid"]) for a in two] == [
            ("flash_bwd_dq", False, [4, 2, 2]),
            ("flash_bwd_dkv", False, [2, 2, 4])]
        assert len(said()) == 6
    finally:
        monkeypatch.delenv("MXNET_TRACE")
        tracing.reconfigure()
        tracing.reset()
    # off: a fresh compile says nothing
    flash_attention(q, k, v, True, None)
    assert said() == []


@pytest.mark.parametrize("causal,kinds", [
    # (diag_strip, tiles_full, tiles_diag, tiles_masked) of flash_fwd and
    # flash_bwd_dkv_dq: 3 x 3 tiles of 128, a head's live ones by kind
    (True, [(128, 3, 3, 0), (128, 3, 3, 0)]),
    (False, [(0, 0, 0, 0), (0, 0, 0, 0)]),
])
def test_geometry_instant_counts_the_tiles_by_kind(monkeypatch, causal,
                                                   kinds):
    """How often the strips engage: the instant of each kernel says the
    strips' rows and one head's live tiles below the diagonal, on it and
    masked whole; a call that is not causal strips nothing."""
    from mxnet_tpu import tracing
    q = jnp.ones((1, 4, 384, 40), jnp.float32)
    k = v = jnp.ones((1, 2, 384, 40), jnp.float32)
    monkeypatch.setenv("MXNET_TRACE", "1")
    tracing.reconfigure()
    try:
        tracing.reset()
        jax.grad(lambda a, b, c: jnp.sum(flash_attention(
            a, b, c, causal, None, 128, 128)), argnums=(0, 1, 2))(q, k, v)
        got = [r["args"] for r in tracing.ring_records()
               if r["name"] == "mx.attention.geometry"]
    finally:
        monkeypatch.delenv("MXNET_TRACE")
        tracing.reconfigure()
        tracing.reset()
    assert [a["kernel"] for a in got] == ["flash_fwd", "flash_bwd_dkv_dq"]
    assert [(a["diag_strip"], a["tiles_full"], a["tiles_diag"],
             a["tiles_masked"]) for a in got] == kinds


# --- Ulysses all-to-all sequence parallelism (parallel/ulysses.py) ---------

def _full_attn(q, k, v, causal=False):
    # same oracle as every other test in this file
    return np.asarray(_attn_reference(jnp.asarray(q), jnp.asarray(k),
                                      jnp.asarray(v), causal, None))


@pytest.mark.parametrize("causal", [False, True])
def test_ulysses_matches_full_attention(causal):
    import jax
    from mxnet_tpu import parallel as par
    from mxnet_tpu.parallel.ulysses import ulysses_attention
    n = 4
    mesh = par.make_mesh(dp=1, sp=n, devices=jax.devices()[:n])
    rs = np.random.RandomState(0)
    B, H, S, D = 2, 8, 32, 16
    q, k, v = (rs.randn(B, H, S, D).astype('float32') for _ in range(3))
    qs, ks, vs = (par.shard_seq(np.asarray(x), mesh) for x in (q, k, v))
    out = np.asarray(ulysses_attention(qs, ks, vs, mesh, causal=causal))
    ref = _full_attn(q, k, v, causal=causal)
    np.testing.assert_allclose(out, ref, rtol=2e-4, atol=2e-5)


def test_ulysses_grad_and_ring_agreement():
    import jax
    import jax.numpy as jnp
    from mxnet_tpu import parallel as par
    from mxnet_tpu.parallel.ulysses import ulysses_attention
    n = 4
    mesh = par.make_mesh(dp=1, sp=n, devices=jax.devices()[:n])
    rs = np.random.RandomState(1)
    B, H, S, D = 1, 4, 16, 8
    q, k, v = (rs.randn(B, H, S, D).astype('float32') for _ in range(3))
    qs, ks, vs = (par.shard_seq(np.asarray(x), mesh) for x in (q, k, v))

    def loss_u(a, b, c):
        return jnp.sum(ulysses_attention(a, b, c, mesh, causal=True) ** 2)

    def loss_r(a, b, c):
        return jnp.sum(par.ring_attention(a, b, c, mesh, causal=True) ** 2)

    gu = jax.jit(jax.grad(loss_u, argnums=(0, 1, 2)))(qs, ks, vs)
    gr = jax.jit(jax.grad(loss_r, argnums=(0, 1, 2)))(qs, ks, vs)
    for a, b in zip(gu, gr):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=3e-4, atol=3e-5)


def test_ulysses_head_divisibility_error():
    import jax
    from mxnet_tpu import parallel as par
    from mxnet_tpu.parallel.ulysses import ulysses_attention
    mesh = par.make_mesh(dp=1, sp=4, devices=jax.devices()[:4])
    q = np.zeros((1, 2, 16, 8), 'float32')  # 2 heads < sp=4
    with pytest.raises(Exception):
        ulysses_attention(q, q, q, mesh)


# --- grouped-query / multi-query attention (GQA) ---------------------------

@pytest.mark.parametrize("hk,causal", [(2, False), (2, True), (1, True)])
def test_flash_gqa_matches_repeated_kv(hk, causal):
    """flash_attention with Hk kv heads == full attention with the kv
    heads explicitly repeated per group (Hk=1 is MQA)."""
    rs = np.random.RandomState(0)
    B, H, S, D = 2, 4, 48, 16
    q = rs.randn(B, H, S, D).astype('float32')
    k = rs.randn(B, hk, S, D).astype('float32')
    v = rs.randn(B, hk, S, D).astype('float32')
    out = np.asarray(flash_attention(jnp.asarray(q), jnp.asarray(k),
                                     jnp.asarray(v), causal, None))
    g = H // hk
    ref = np.asarray(_attn_reference(
        jnp.asarray(q), jnp.asarray(np.repeat(k, g, axis=1)),
        jnp.asarray(np.repeat(v, g, axis=1)), causal, None))
    np.testing.assert_allclose(out, ref, rtol=2e-4, atol=2e-5)


def test_flash_gqa_gradients():
    """GQA backward: dq/dk/dv match autodiff through the repeated-KV
    reference (dk/dv sum over the group's query heads)."""
    rs = np.random.RandomState(1)
    B, H, Hk, S, D = 1, 4, 2, 32, 8
    q = jnp.asarray(rs.randn(B, H, S, D).astype('float32'))
    k = jnp.asarray(rs.randn(B, Hk, S, D).astype('float32'))
    v = jnp.asarray(rs.randn(B, Hk, S, D).astype('float32'))

    def loss_flash(q_, k_, v_):
        return jnp.sum(flash_attention(q_, k_, v_, True, None) ** 2)

    def loss_ref(q_, k_, v_):
        g = H // Hk
        return jnp.sum(_attn_reference(
            q_, jnp.repeat(k_, g, axis=1), jnp.repeat(v_, g, axis=1),
            True, None) ** 2)

    gf = jax.grad(loss_flash, argnums=(0, 1, 2))(q, k, v)
    gr = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(gf, gr):
        assert a.shape == b.shape
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=3e-4, atol=3e-4)


def test_flash_gqa_bad_heads_raises():
    q = jnp.zeros((1, 4, 16, 8))
    k = jnp.zeros((1, 3, 16, 8))
    with pytest.raises(ValueError):
        flash_attention(q, k, k, False, None)


def test_ring_and_ulysses_accept_gqa_inputs():
    import jax
    from mxnet_tpu import parallel as par
    from mxnet_tpu.parallel.ulysses import ulysses_attention
    n = 4
    mesh = par.make_mesh(dp=1, sp=n, devices=jax.devices()[:n])
    rs = np.random.RandomState(5)
    B, H, Hk, S, D = 1, 8, 2, 32, 16
    q = rs.randn(B, H, S, D).astype('float32')
    k = rs.randn(B, Hk, S, D).astype('float32')
    v = rs.randn(B, Hk, S, D).astype('float32')
    ref = _full_attn(q, np.repeat(k, H // Hk, 1), np.repeat(v, H // Hk, 1),
                     causal=True)
    qs = par.shard_seq(np.asarray(q), mesh)
    ks = par.shard_seq(np.asarray(k), mesh)
    vs = par.shard_seq(np.asarray(v), mesh)
    out_r = np.asarray(par.ring_attention(qs, ks, vs, mesh, causal=True))
    out_u = np.asarray(ulysses_attention(qs, ks, vs, mesh, causal=True))
    np.testing.assert_allclose(out_r, ref, rtol=2e-4, atol=2e-5)
    np.testing.assert_allclose(out_u, ref, rtol=2e-4, atol=2e-5)


def test_ulysses_gqa_compact_path_and_ring_dp_fold():
    """Hk divisible by the group size: Ulysses moves the COMPACT kv form
    through the all-to-all; ring's query-group fold works under dp
    sharding (local batch differs from global)."""
    import jax
    from mxnet_tpu import parallel as par
    from mxnet_tpu.parallel.ulysses import ulysses_attention
    from jax.sharding import NamedSharding, PartitionSpec as P
    rs = np.random.RandomState(6)
    B, H, Hk, S, D = 4, 8, 4, 32, 16
    q = rs.randn(B, H, S, D).astype('float32')
    k = rs.randn(B, Hk, S, D).astype('float32')
    v = rs.randn(B, Hk, S, D).astype('float32')
    ref = _full_attn(q, np.repeat(k, H // Hk, 1),
                     np.repeat(v, H // Hk, 1), causal=True)

    mesh = par.make_mesh(dp=2, sp=4)
    sh = NamedSharding(mesh, P('dp', None, 'sp', None))
    qs, ks, vs = (jax.device_put(np.asarray(x), sh) for x in (q, k, v))
    out_u = np.asarray(ulysses_attention(qs, ks, vs, mesh, causal=True))
    np.testing.assert_allclose(out_u, ref, rtol=2e-4, atol=2e-5)
    out_r = np.asarray(par.ring_attention(qs, ks, vs, mesh, causal=True))
    np.testing.assert_allclose(out_r, ref, rtol=2e-4, atol=2e-5)


@pytest.mark.parametrize("impl", [None, "xla"])
def test_registry_op_always_runs_the_flash_kernels(monkeypatch, impl):
    """The registry op (mx.nd / mx.sym / gluon) is the Pallas kernels and
    nothing else: no environment variable and no table picks another
    implementation, and the result equals the XLA reference."""
    from mxnet_tpu.ops import attention as att

    if impl is None:
        monkeypatch.delenv("MXNET_ATTENTION_IMPL", raising=False)
    else:
        monkeypatch.setenv("MXNET_ATTENTION_IMPL", impl)
    q, k, v = _rand_qkv(S=32, D=16)
    traced = jax.make_jaxpr(
        lambda q, k, v: att._flash_attention_op(q, k, v, causal=True))(
            q, k, v)
    assert "pallas_call" in str(traced)
    out = mx.nd.flash_attention(mx.nd.NDArray(q), mx.nd.NDArray(k),
                                mx.nd.NDArray(v), causal=True)
    np.testing.assert_allclose(
        out.asnumpy(), np.asarray(att._attn_reference(q, k, v, True, None)),
        rtol=1e-5, atol=1e-5)
    for gone in ("pick_attention_impl", "_load_dispatch_table",
                 "pick_attention_config"):
        assert not hasattr(att, gone)


# --- the program the chip compiles ------------------------------------------
# The interpreter (every test above) shows the traced program computes the
# right numbers; these show Mosaic is handed a program it accepts, without a
# chip: the Pallas->Mosaic lowering of each kernel for the TPU platform with
# interpret=False, under the package's own x64 setting.  At the seed this
# failed twice over: lse/delta blocks of shape (1, block_q) over a 2-D array
# broke the (8, 128) block rule, and with x64 on every Python int in an index
# map or loop bound entered as int64, which Mosaic does not have.
def _tpu_lowered(fn, *avals):
    return jax.jit(fn).trace(*avals).lower(
        lowering_platforms=("tpu",)).as_text()


@pytest.mark.parametrize("kernel", ["fwd", "fwd_lse", "bwd"])
@pytest.mark.parametrize("D,Hk", [(64, 4), (64, 1), (128, 4), (128, 2)])
@pytest.mark.parametrize("S", [512, 1024, 4096])
def test_flash_kernels_cross_lower_for_tpu(kernel, D, Hk, S):
    """S 512 is one derived tile; S 1024 the forward's 1024-row tile and
    2 x 2 of the backward's 512; S 4096 several of both.  The backward is
    the merged kernel wherever dQ's accumulator fits."""
    from mxnet_tpu.ops import attention as A
    assert jax.config.jax_enable_x64       # the package's setting, not ours
    B, H = 2, 4
    q = jax.ShapeDtypeStruct((B, H, S, D), jnp.bfloat16)
    kv = jax.ShapeDtypeStruct((B, Hk, S, D), jnp.bfloat16)
    lse = jax.ShapeDtypeStruct((B, H, S), jnp.float32)
    if kernel == "bwd":
        txt = _tpu_lowered(
            lambda q, k, v, o, l, g: A._flash_bwd(
                q, k, v, o, l, g, causal=True, interpret=False),
            q, kv, kv, q, lse, q)
        # H 4 / Hk 1 at S 4096 is 16384 rows of dQ a KV head: over the
        # count, so that one lowers the two kernels
        geo = A._geometry(q, kv, None, None, forward=False, causal=True)
        merged = geo.merged
        assert merged == ((S, Hk) != (4096, 1))
        names = ["flash_bwd_dkv_dq"] if merged \
            else ["flash_bwd_dq", "flash_bwd_dkv"]
    else:
        txt = _tpu_lowered(
            lambda q, k, v: A._flash_fwd(
                q, k, v, causal=True, interpret=False,
                return_lse=kernel == "fwd_lse"),
            q, kv, kv)
        names = ["flash_fwd"]
        geo = A._geometry(q, kv, None, None, forward=True, causal=True)
    # the backward runs its diagonal in strips, the forward where its
    # tile is 512 rows or fewer or the sequence more than one tile
    assert geo.diag_strip == (128 if kernel == "bwd" or S == 512 else
                              512 if S == 4096 else 0)
    assert geo.block_q == geo.block_k
    assert txt.count("tpu_custom_call") == len(names)
    for n in names:
        assert 'kernel_name = "%s"' % n in txt
