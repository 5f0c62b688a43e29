"""Mixture-of-Experts with expert parallelism (ep mesh axis).

NEW capability relative to the reference (SURVEY.md §2.5: expert
parallelism ABSENT — the reference predates MoE).  The TPU-native design
is the Mesh-TensorFlow/GShard dense-dispatch formulation: top-k gating
builds dispatch/combine tensors, expert FFNs are einsums over an
expert-major (E, capacity, d) layout, and sharding the E axis over the
mesh's ``ep`` axis makes GSPMD insert the token all-to-alls.  Everything
is static-shaped (capacity-bounded routing) so XLA tiles the expert
matmuls onto the MXU.

Composable three ways: the raw jax function (`moe_ffn`), the registered
op (`_contrib_MoE` — mx.nd / mx.sym), and `gluon.nn` via the op.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from ..base import tag_for_remat as _ckpt_name

from .registry import register


def _top_k_gating(logits, k, capacity):
    """logits (T, E) → dispatch (T, E, C) one-hot, combine (T, E, C).

    Top-k softmax gating with capacity-bounded position assignment
    (GShard's expert capacity: tokens beyond C per expert are dropped —
    their combine weights are zero, so they pass through as zeros and the
    residual connection carries them)."""
    T, E = logits.shape
    probs = jax.nn.softmax(logits, axis=-1)                # (T, E)
    # tie-safe top-k: iterative argmax + one-hot (a >=threshold mask
    # would select ALL tied experts, e.g. with uniform gates)
    mask = jnp.zeros_like(probs)
    work = probs
    for _ in range(k):
        sel = jax.nn.one_hot(jnp.argmax(work, axis=-1), E,
                             dtype=probs.dtype)            # (T, E)
        mask = mask + sel
        work = jnp.where(sel > 0, -jnp.inf, work)
    gates = probs * mask
    # renormalize over the selected experts
    gates = gates / jnp.maximum(gates.sum(-1, keepdims=True), 1e-9)
    # position of each token within each expert's capacity (by token order)
    pos = jnp.cumsum(mask, axis=0) * mask - 1.0            # (T, E)
    in_cap = (pos >= 0) & (pos < capacity)
    pos = jnp.where(in_cap, pos, 0).astype(jnp.int32)
    onehot_c = jax.nn.one_hot(pos, capacity, dtype=probs.dtype)  # (T,E,C)
    onehot_c = onehot_c * in_cap.astype(probs.dtype)[..., None]
    dispatch = onehot_c * mask[..., None]                  # (T, E, C)
    combine = dispatch * gates[..., None]                  # (T, E, C)
    return dispatch, combine


def moe_ffn(x, gate_w, w1, b1, w2, b2, num_experts, k=1,
            capacity_factor=2.0, activation="relu"):
    """MoE feed-forward.  x (..., d); gate_w (d, E);
    w1 (E, d, f), b1 (E, f), w2 (E, f, d), b2 (E, d) → (..., d).

    Shard w1/w2/b1/b2 with PartitionSpec('ep', ...) and GSPMD turns the
    ecd-axis einsums into expert-parallel compute with all-to-all routing.
    """
    orig_shape = x.shape
    d = orig_shape[-1]
    xt = x.reshape(-1, d)                                  # (T, d)
    T = xt.shape[0]
    capacity = max(1, int(capacity_factor * T * k / num_experts))
    logits = xt @ gate_w                                   # (T, E)
    dispatch, combine = _top_k_gating(logits, k, capacity)
    # route tokens to experts: (E, C, d)
    expert_in = jnp.einsum("tec,td->ecd", dispatch, xt)
    # tagged so MXNET_REMAT_POLICY=save_matmuls keeps the expensive expert
    # matmul outputs and recomputes only the activation/bias chains
    h = _ckpt_name(jnp.einsum("ecd,edf->ecf", expert_in, w1),
                   "matmul_out", *w1.shape[1:]) + b1[:, None, :]
    if activation == "relu":
        h = jax.nn.relu(h)
    elif activation == "gelu":
        h = jax.nn.gelu(h)
    expert_out = _ckpt_name(jnp.einsum("ecf,efd->ecd", h, w2),
                            "matmul_out", *w2.shape[1:]) + b2[:, None, :]
    out = jnp.einsum("tec,ecd->td", combine, expert_out)   # (T, d)
    return out.reshape(orig_shape)


@register("_contrib_MoE",
          arg_names=["data", "gate_weight", "expert_w1", "expert_b1",
                     "expert_w2", "expert_b2"],
          aliases=("moe_ffn",),
          attr_defaults={"num_experts": 0, "k": 1,
                         "capacity_factor": 2.0, "activation": "relu"})
def _moe_op(data, gate_weight, expert_w1, expert_b1, expert_w2, expert_b2,
            num_experts=0, k=1, capacity_factor=2.0, activation="relu",
            **kw):
    """Registry entry: MoE FFN usable from mx.nd / mx.sym / gluon.
    num_experts defaults from gate_weight's last dim."""
    E = int(num_experts) or int(gate_weight.shape[-1])
    return moe_ffn(data, gate_weight, expert_w1, expert_b1, expert_w2,
                   expert_b2, E, int(k), float(capacity_factor),
                   activation)
