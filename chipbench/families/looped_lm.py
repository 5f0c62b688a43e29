"""Looped decoder-only LM, Ouro's layout: Zhu et al. 2025, "Scaling Latent
Reasoning via Looped Language Models" (ByteDance/Ouro-2.6B ``config.json``,
``model_type`` ``ouro``): a stack of sandwich-normed, bias-free RoPE /
SwiGLU layers and the final RMSNorm, run ``total_ut_steps`` times over its
own output with the same weights; an untied head and an exit gate read
every loop step's state; the objective is the expected cross-entropy under
the exit distribution plus ``beta`` times its negative entropy.  What the
published config does not say is listed under ``assumed`` in the
configuration file.  ``build`` asks the program for its symbol by a name
only a program with a loop node has; the plain reference, the FLOP count
and the flash kernels' operations and bytes are the yardstick's own."""
import math

OUTPUT_WEIGHT = "lm_head_weight"
# query rows a block of the reference's attention: scores are held for one
# block of queries against all keys, not for S x S
QUERY_BLOCK = 1024


def _sizes(cfg, traffic):
    b = cfg["builder"]
    d, h = int(b["d_model"]), int(b["num_heads"])
    return {"V": int(b["vocab_size"]), "L": int(b["num_layers"]), "d": d,
            "H": h, "hd": d // h, "d_ff": int(b["d_ff"]),
            "T": int(b["loop_steps"]), "eps": float(b["norm_eps"]),
            "theta": float(b["rope_base"]), "beta": float(b["exit_beta"]),
            "B": int(traffic["batch"]), "S": int(traffic["seq_len"])}


def build(cfg, traffic):
    # the builder's name is the mechanism's: a program without the loop
    # node has no such function and fails here, before anything compiles
    from mxnet_tpu.models import looped_transformer_lm
    z = _sizes(cfg, traffic)
    kw = dict(cfg["builder"])
    sym = looped_transformer_lm(kw.pop("vocab_size"), z["S"], **kw)
    return {"symbol": sym,
            "data_shapes": [("data", (z["B"], z["S"]))],
            "label_shapes": [("softmax_label", (z["B"], z["S"]))],
            "items_per_step": z["B"] * z["S"],
            "output_weight": OUTPUT_WEIGHT}


def make_batch(cfg, traffic, key):
    """One resident batch, made on the device: token ids uniform over the
    whole vocabulary, int32, and independent next-token labels in float32
    as MXNet iterators give (ids up to V - 1 = 49151: the program may not
    round them)."""
    import jax
    import jax.numpy as jnp
    z = _sizes(cfg, traffic)

    @jax.jit
    def make(key):
        kx, ky = jax.random.split(key)
        x = jax.random.randint(kx, (z["B"], z["S"]), 0, z["V"], jnp.int32)
        y = jax.random.randint(ky, (z["B"], z["S"]), 0, z["V"], jnp.int32)
        return x, y.astype(jnp.float32)
    return make(key)


def output_sample(cfg, traffic, seed):
    """Rows of the (B*S, V) output that are compared: a seeded sample of
    at most 256 positions, sorted."""
    import numpy as np
    z = _sizes(cfg, traffic)
    rows = z["B"] * z["S"]
    rng = np.random.default_rng(int(seed))
    return np.sort(rng.choice(rows, size=min(256, rows), replace=False)
                   ).astype(np.int32)


# -- the plain reference ----------------------------------------------------
def _objective(cfg, traffic, params, data, label, cd, sample):
    """(objective, (mean token CE of the last loop step, sampled rows of
    its softmax)).  ``cd`` is the compute dtype: parameters are cast to it
    where they meet an activation, activations stay in it; RMSNorm
    statistics, the rotary table, the attention softmax, the gate's
    sigmoid, the exit distribution and every cross-entropy are taken in
    float32."""
    import jax
    import jax.numpy as jnp
    f32 = jnp.float32
    z = _sizes(cfg, traffic)
    B, S, H, hd, d, T = z["B"], z["S"], z["H"], z["hd"], z["d"], z["T"]
    N = B * S

    def rms_norm(x, gain):
        xf = x.astype(f32)
        y = xf * jax.lax.rsqrt(jnp.mean(jnp.square(xf), axis=-1,
                                        keepdims=True) + f32(z["eps"]))
        return (y * gain.astype(f32)).astype(cd)

    # rotary table, half-split form: pair (i, i + hd/2) turns by
    # position * theta ** (-2 i / hd)
    inv = jnp.exp(jnp.arange(hd // 2, dtype=f32)
                  * f32(-2.0 * math.log(z["theta"]) / hd))
    ang = jnp.arange(S, dtype=f32)[:, None] * inv[None, :]
    cos, sin = jnp.cos(ang).astype(cd), jnp.sin(ang).astype(cd)

    def rope(t):                                    # (B, H, S, hd)
        t1, t2 = t[..., :hd // 2], t[..., hd // 2:]
        return jnp.concatenate([t1 * cos - t2 * sin,
                                t2 * cos + t1 * sin], axis=-1)

    qb = min(S, QUERY_BLOCK)
    keys = jnp.arange(S)

    def attention(q, k, v):
        """Causal softmax(q k^T / sqrt(hd)) v, a block of queries at a
        time."""
        def block(_, xs):
            i, qi = xs                              # (B, H, qb, hd)
            s = jnp.einsum("bhqd,bhkd->bhqk", qi, k).astype(f32) \
                * f32(1.0 / math.sqrt(hd))
            rows = i * qb + jnp.arange(qb)
            s = jnp.where(rows[:, None] >= keys[None, :], s, f32(-1e30))
            return None, jnp.einsum(
                "bhqk,bhkd->bhqd", jax.nn.softmax(s, axis=-1).astype(cd), v)
        blocks = q.reshape(B, H, S // qb, qb, hd).transpose(2, 0, 1, 3, 4)
        _, o = jax.lax.scan(jax.checkpoint(block), None,
                            (jnp.arange(S // qb), blocks))
        return o.transpose(1, 2, 0, 3, 4).reshape(B, H, S, hd)

    def layer(x, w):
        """One sandwich-normed layer; ``w`` holds its tensors by the part
        of their name after ``layer<i>_``."""
        def dense(x, name):
            return x @ w[name + "_weight"].astype(cd).T

        qkv = dense(rms_norm(x, w["ln1_gamma"]).reshape(N, d), "qkv")
        q, k, v = (qkv[:, j * d:(j + 1) * d].reshape(B, S, H, hd)
                   .transpose(0, 2, 1, 3) for j in range(3))
        a = attention(rope(q), rope(k), v)
        a = dense(a.transpose(0, 2, 1, 3).reshape(N, d), "proj")
        x = x + rms_norm(a.reshape(B, S, d), w["ln1_post_gamma"])
        both = dense(rms_norm(x, w["ln2_gamma"]).reshape(N, d), "fc1")
        gate, lin = both[:, :z["d_ff"]], both[:, z["d_ff"]:]
        f = dense(gate * jax.nn.sigmoid(gate) * lin, "fc2")
        return x + rms_norm(f.reshape(B, S, d), w["ln2_post_gamma"]), None

    parts = sorted(n[len("layer0_"):] for n in params
                   if n.startswith("layer0_"))
    stacked = {part: jnp.stack([params["layer%d_%s" % (i, part)]
                                for i in range(z["L"])]) for part in parts}
    head = params["lm_head_weight"]
    lab = label.astype(jnp.int32).reshape(-1)

    def loop_step(h, _):
        """The whole stack and the final norm once more over ``h``; this
        step's per-token cross-entropy and exit-gate logit."""
        h, _ = jax.lax.scan(jax.checkpoint(layer), h, stacked)
        h = rms_norm(h, params["final_norm_gamma"])
        flat = h.reshape(N, d)
        logp = jax.nn.log_softmax((flat @ head.astype(cd).T).astype(f32),
                                  axis=-1)
        ce = -jnp.take_along_axis(logp, lab[:, None], axis=1)[:, 0]
        gate = flat @ params["exit_gate_weight"].astype(cd).T \
            + params["exit_gate_bias"].astype(cd)
        return h, (ce, gate[:, 0].astype(f32))

    h0 = params["tok_embed_weight"].astype(cd)[data.astype(jnp.int32)]
    h_last, (ce, gate) = jax.lax.scan(jax.checkpoint(loop_step), h0, None,
                                      length=T)            # (T, N) each
    # exit distribution, in log space: p_t = lambda_t prod_{j<t} (1 -
    # lambda_j), the rest of the mass at the last step
    log_stay = jax.nn.log_sigmoid(-gate)[:-1]
    before = jnp.concatenate([jnp.zeros((1, N), f32),
                              jnp.cumsum(log_stay, axis=0)])
    log_p = before + jnp.concatenate([jax.nn.log_sigmoid(gate)[:-1],
                                      jnp.zeros((1, N), f32)])
    per_token = jnp.sum(jnp.exp(log_p) * (ce + f32(z["beta"]) * log_p),
                        axis=0)
    rows = h_last.reshape(N, d)[sample]
    probs = jax.nn.softmax((rows @ head.astype(cd).T).astype(f32), axis=-1)
    # summed over each sequence's positions, averaged over the batch
    return jnp.sum(per_token) / f32(B), (jnp.mean(ce[-1]), probs)


def reference(cfg, traffic, params, data, label, compute_dtype, sample):
    """(mean token cross-entropy of the last loop step, sampled rows of
    its softmax, {tensor: delta of one SGD-momentum step under the whole
    objective}) from float32 master ``params``; plain jax.numpy, nothing
    of ``mxnet_tpu``."""
    import jax
    import jax.numpy as jnp
    from chipbench.common import sgd_momentum_delta
    cd = jnp.dtype(compute_dtype)

    # the batch and the sample are arguments: a closed-over array would be
    # a constant of the program, and no other seed would find it cached
    @jax.jit
    def step(p, data, label, sample):
        with jax.default_matmul_precision("highest"):
            grads, (loss, out) = jax.grad(
                lambda p: _objective(cfg, traffic, p, data, label, cd,
                                     sample), has_aux=True)(p)
        return loss, out, sgd_momentum_delta(p, grads, cfg["optimizer"])
    return step(params, data, label, jnp.asarray(sample, jnp.int32))


# -- operations the model requires -------------------------------------------
def model_flops(cfg, traffic):
    """Per step, forward + backward = 3 x forward, nothing recomputed.
    Forward: every loop step applies every layer (2 x tokens x its matmul
    parameters, 4 d^2 + 3 d d_ff, and causal attention's two matmuls over
    the lower triangle, 2 x S^2 x d a sequence) and the head (2 x tokens
    x d x V); the gate, norms, rotations and lookups are not counted."""
    z = _sizes(cfg, traffic)
    tokens = z["B"] * z["S"]
    per_layer = 4 * z["d"] * z["d"] + 3 * z["d"] * z["d_ff"]
    applied = z["T"] * z["L"]
    dense = 2.0 * tokens * (applied * per_layer + z["T"] * z["d"] * z["V"])
    attn = applied * z["B"] * 2.0 * z["S"] * z["S"] * z["d"]
    return 3.0 * (dense + attn)


def kernel_costs(cfg, traffic):
    """{kernel name prefix: {"flops", "bytes", "calls_per_step"}} for one
    call of each flash kernel at this cell's shapes, by the work attention
    requires over the causal half of the S x S score matrix: 2 matmuls
    forward, 5 backward (dV, dP, dQ, dK and the scores once; a kernel that
    builds the scores again does more than is counted).  The backward is
    the one kernel whose name starts ``flash_bwd_dkv`` wherever dQ's
    accumulator fits (ops/attention.py); the split pair's ``flash_bwd_dq``
    is priced for where it does not.  The rematerialised forward calls the
    forward kernel once more a layer application: calls like any other."""
    z = _sizes(cfg, traffic)
    bh = z["B"] * z["H"]
    matmul = 2.0 * z["S"] * z["S"] * z["hd"] / 2.0 * bh   # one, causal half
    tensor = bh * z["S"] * z["hd"] * 2.0                  # one bf16 tensor
    row = bh * z["S"] * 4.0                               # one f32 row stat
    applied = z["T"] * z["L"]
    return {
        "flash_fwd": {"flops": 2 * matmul, "bytes": 4 * tensor + row,
                      "calls_per_step": 2 * applied},
        "flash_bwd_dq": {"flops": 3 * matmul,
                         "bytes": 5 * tensor + 2 * row,
                         "calls_per_step": 0},
        "flash_bwd_dkv": {"flops": 5 * matmul,
                          "bytes": 8 * tensor + 2 * row,
                          "calls_per_step": applied},
    }
