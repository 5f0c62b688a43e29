"""Ulysses sequence parallelism — all-to-all head/sequence re-sharding.

The second long-context strategy alongside ring attention (SURVEY.md
§5.7 asks for "ring attention or all-to-all sequence/context
parallelism"; DeepSpeed-Ulysses is the public reference for the
pattern).  Inputs arrive sequence-sharded (each of the ``sp`` devices
holds S/n timesteps of EVERY head); one ``lax.all_to_all`` re-shards to
head-sharded (each device holds H/n heads of the FULL sequence), plain
full attention runs per head group — any masking/dropout composes
freely because the whole sequence is local — and a second all-to-all
restores sequence sharding.

Trade-off vs ring: two all-to-alls of the whole activation (bisection
bandwidth) instead of n ppermute hops, O(S²/n) score memory instead of
O(S²/n²), but no per-step softmax bookkeeping and H must divide by n.
Both collectives ride ICI on a TPU mesh.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import lax
from jax import shard_map
from jax.sharding import PartitionSpec as P

from ..base import MXNetError
from ..ops.attention import _attn_reference
from .mesh import mesh_shape


def ulysses_attention(q, k, v, mesh, causal=False, scale=None,
                      axis_name="sp", spec=None):
    """Exact attention with seq-sharded q/k/v: (B, H, S, D), S and H both
    divisible by the sp size; returns (B, H, S, D) sharded like q.
    """
    if axis_name not in mesh.axis_names:
        raise MXNetError(f"mesh has no axis {axis_name!r}")
    n = mesh_shape(mesh)[axis_name]
    B, H, S, D = q.shape
    if S % n:
        raise MXNetError(f"seq len {S} not divisible by {axis_name}={n}")
    Hk = k.shape[1]
    if Hk != H:
        # GQA inputs.  When the kv heads themselves split evenly over the
        # group (Hk % n == 0), the all-to-all moves the COMPACT kv form:
        # contiguous head-block splits keep the q-head -> kv-head (h // g)
        # pairing aligned per device, and the local oracle handles grouped
        # heads natively.  Otherwise fall back to repeating kv up to H.
        if H % Hk:
            raise MXNetError(
                f"q heads {H} not divisible by kv heads {Hk}")
        if Hk % n:
            from ..ops.attention import gqa_repeat_kv
            k, v = gqa_repeat_kv(q, k, v)
    if H % n:
        raise MXNetError(
            f"ulysses needs heads ({H}) divisible by {axis_name}={n}; "
            "use ring_attention for head counts below the ring size")
    if scale is None:
        scale = 1.0 / (D ** 0.5)
    if spec is None:
        spec = P("dp", None, axis_name, None)

    def local(q, k, v):
        # local shapes (B, H, S/n, D), seq-sharded
        # all-to-all: split heads across the group, gather the sequence —
        # local becomes (B, H/n, S, D), head-sharded
        def seq2head(x):
            return lax.all_to_all(x, axis_name, split_axis=1,
                                  concat_axis=2, tiled=True)

        def head2seq(x):
            return lax.all_to_all(x, axis_name, split_axis=2,
                                  concat_axis=1, tiled=True)

        qh = seq2head(q)
        kh = seq2head(k)
        vh = seq2head(v)
        # full attention per local head group — the one exact-attention
        # implementation (ops/attention.py) serves ring's backward, the
        # flash kernel's oracle, and this path
        out = _attn_reference(qh, kh, vh, causal, scale)
        return head2seq(out)

    fn = shard_map(local, mesh=mesh, in_specs=(spec, spec, spec),
                   out_specs=spec)
    return fn(q, k, v)
