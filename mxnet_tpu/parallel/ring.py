"""Ring attention — context/sequence parallelism over the ``sp`` mesh axis.

NEW capability vs the reference (SURVEY.md §5.7: sequence parallelism is
ABSENT in MXNet 0.12; the closest thing is BucketingModule).  Q/K/V are
sharded along the sequence dimension across the ``sp`` ring; each step
every device computes blockwise attention of its local Q against the K/V
shard it currently holds, then rotates K/V one hop with
``jax.lax.ppermute`` — the collective rides ICI neighbor links, and the
online-softmax accumulator makes the result exactly equal to full
attention.  Peak memory per chip is O(S/n · S/n) scores instead of O(S²).

Causality is handled by global position masks derived from each shard's
rotating source index, so causal LM training works at any ring size.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import NamedSharding, PartitionSpec as P
from jax import shard_map

from ..base import MXNetError
from .mesh import mesh_shape

_NEG_INF = -1e30


def _block_attn(q, k, v, scale, q_pos, k_pos, causal, m, l, acc):
    """One online-softmax accumulation step.
    q: (B,H,Sq,D) local; k/v: (B,H,Sk,D) current ring shard."""
    s = jnp.einsum("bhqd,bhkd->bhqk", q.astype(jnp.float32),
                   k.astype(jnp.float32)) * scale
    if causal:
        mask = k_pos[None, :] <= q_pos[:, None]
        s = jnp.where(mask[None, None], s, _NEG_INF)
    m_new = jnp.maximum(m, s.max(axis=-1, keepdims=True))
    p = jnp.exp(s - m_new)
    alpha = jnp.exp(m - m_new)
    l = l * alpha + p.sum(axis=-1, keepdims=True)
    acc = acc * alpha + jnp.einsum("bhqk,bhkd->bhqd", p,
                                   v.astype(jnp.float32))
    return m_new, l, acc


def ring_attention(q, k, v, mesh, causal=False, scale=None,
                   axis_name="sp", spec=None):
    """Exact attention with seq-sharded Q/K/V.  q/k/v: (B, H, S, D) with S
    divisible by the sp ring size; returns (B, H, S, D) sharded the same
    way."""
    if axis_name not in mesh.axis_names:
        raise MXNetError(f"mesh has no axis {axis_name!r}")
    n = mesh_shape(mesh)[axis_name]
    B, H, S, D = q.shape
    Hk = k.shape[1]
    if Hk != H and H % Hk:
        raise MXNetError(
            f"q heads {H} not divisible by kv heads {Hk}")
    gqa = H // Hk  # GQA group size: handled by FOLDING each group's query
    # heads into the query length (attention rows are independent), so the
    # ring rotates the compact Hk-head K/V — no repeated-KV traffic
    if S % n:
        raise MXNetError(f"seq len {S} not divisible by {axis_name}={n}")
    if scale is None:
        scale = 1.0 / (D ** 0.5)
    chunk = S // n
    if spec is None:
        spec = P("dp", None, axis_name, None)  # batch over dp, seq over sp
    spec_axes = tuple({a for entry in spec if entry is not None
                       for a in ((entry,) if isinstance(entry, str)
                                 else entry)})

    def local(q, k, v):
        # q: (B, H, S/n, D); k/v: (B, Hk, S/n, D) — this device's shard.
        # GQA fold: group query heads into the row dimension so the
        # blockwise step runs at Hk heads against the compact K/V
        if gqa > 1:
            # q.shape[0] = LOCAL batch (dp shards it inside shard_map)
            q = q.reshape(q.shape[0], Hk, gqa * chunk, D)
        idx = lax.axis_index(axis_name)
        q_pos = idx * chunk + jnp.arange(chunk)
        if gqa > 1:
            q_pos = jnp.tile(q_pos, gqa)  # row r is position q_pos[r%chunk]
        m = jnp.full(q.shape[:3] + (1,), _NEG_INF, jnp.float32)
        l = jnp.zeros(q.shape[:3] + (1,), jnp.float32)
        acc = jnp.zeros(q.shape, jnp.float32)
        # accumulators are per-shard state: mark them device-varying on
        # every sharded axis so the fori carry types stay consistent
        m, l, acc = (lax.pcast(x, spec_axes, to="varying")
                     for x in (m, l, acc))

        def step(s, carry):
            k_cur, v_cur, m, l, acc = carry
            # after s forward rotations, we hold the shard that started
            # on device (idx - s) mod n
            src = (idx - s) % n
            k_pos = src * chunk + jnp.arange(chunk)
            m, l, acc = _block_attn(q, k_cur, v_cur, scale, q_pos, k_pos,
                                    causal, m, l, acc)
            perm = [(i, (i + 1) % n) for i in range(n)]
            k_nxt = lax.ppermute(k_cur, axis_name, perm)
            v_nxt = lax.ppermute(v_cur, axis_name, perm)
            return k_nxt, v_nxt, m, l, acc

        k_cur, v_cur, m, l, acc = lax.fori_loop(
            0, n, step, (k, v, m, l, acc))
        out = (acc / jnp.maximum(l, 1e-30)).astype(q.dtype)
        if gqa > 1:
            out = out.reshape(out.shape[0], H, chunk, D)  # unfold groups
        return out

    fn = shard_map(local, mesh=mesh, in_specs=(spec, spec, spec),
                   out_specs=spec)
    return fn(q, k, v)


def shard_seq(x, mesh, axis_name="sp", seq_dim=2):
    """device_put a (…, S, …) array with its seq dim over the sp ring."""
    spec = [None] * x.ndim
    spec[seq_dim] = axis_name
    return jax.device_put(x, NamedSharding(mesh, P(*spec)))
