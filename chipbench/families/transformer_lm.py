"""Decoder-only transformer LM, GPT-2 layout: Radford et al. 2019 (learned
positions, pre-norm blocks, d_ff = 4 d, causal attention), with the
departures the configuration file lists.  ``build`` asks the program for
its symbol; the plain reference, the FLOP count and the flash kernels'
operations and bytes are the yardstick's own."""
import math

OUTPUT_WEIGHT = "lm_head_weight"
LN_EPS = 1e-5


def _sizes(cfg, traffic):
    b = cfg["builder"]
    d, h = int(b["d_model"]), int(b["num_heads"])
    return {"V": int(b["vocab_size"]), "L": int(b["num_layers"]), "d": d,
            "H": h, "hd": d // h, "d_ff": int(b.get("d_ff") or 4 * d),
            "B": int(traffic["batch"]), "S": int(traffic["seq_len"])}


def build(cfg, traffic):
    from mxnet_tpu import models
    z = _sizes(cfg, traffic)
    kw = dict(cfg["builder"])
    sym = models.transformer_lm(kw.pop("vocab_size"), z["S"], **kw)
    return {"symbol": sym,
            "data_shapes": [("data", (z["B"], z["S"]))],
            "label_shapes": [("softmax_label", (z["B"], z["S"]))],
            "items_per_step": z["B"] * z["S"],
            "output_weight": OUTPUT_WEIGHT}


def make_batch(cfg, traffic, key):
    """One resident batch, made on the device: token ids uniform over the
    vocabulary, int32 (a float id would be rounded by a bf16 cast), and
    independent next-token labels in float32 as MXNet iterators give."""
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
def _forward(cfg, traffic, params, data, cd):
    """Probabilities (B*S, V) in float32.  ``cd`` is the compute dtype:
    parameters are cast to it where they meet an activation, activations
    stay in it; layer-norm statistics, the attention softmax and the
    output softmax are taken in float32."""
    import jax
    import jax.numpy as jnp
    f32 = jnp.float32
    z = _sizes(cfg, traffic)
    B, S, H, hd, d = z["B"], z["S"], z["H"], z["hd"], z["d"]

    def layer_norm(x, gamma, beta):
        xf = x.astype(f32)
        mean = jnp.mean(xf, axis=-1, keepdims=True)
        var = jnp.mean(jnp.square(xf - mean), axis=-1, keepdims=True)
        y = (xf - mean) * jax.lax.rsqrt(var + f32(LN_EPS))
        return (y * gamma.astype(f32) + beta.astype(f32)).astype(cd)

    causal = jnp.tril(jnp.ones((S, S), jnp.bool_))

    def block(x, w):
        """One pre-norm block; ``w`` holds this layer's tensors by the
        part of their name after ``layer<i>_``."""
        def dense(x, name):
            return x @ w[name + "_weight"].astype(cd).T \
                + w[name + "_bias"].astype(cd)

        def norm(x, name):
            return layer_norm(x, w[name + "_gamma"], w[name + "_beta"])

        qkv = dense(norm(x, "ln1").reshape(B * S, d), "qkv")
        q, k, v = (qkv[:, j * d:(j + 1) * d].reshape(B, S, H, hd)
                   .transpose(0, 2, 1, 3) for j in range(3))
        s = jnp.einsum("bhqd,bhkd->bhqk", q, k).astype(f32) \
            * f32(1.0 / math.sqrt(hd))
        s = jnp.where(causal, s, f32(-1e30))
        a = jnp.einsum("bhqk,bhkd->bhqd",
                       jax.nn.softmax(s, axis=-1).astype(cd), v)
        a = a.transpose(0, 2, 1, 3).reshape(B * S, d)
        x = x + dense(a, "proj").reshape(B, S, d)
        hdn = dense(norm(x, "ln2").reshape(B * S, d), "fc1")
        hdn = hdn * jax.nn.sigmoid(hdn * jnp.asarray(1.702, cd))
        return x + dense(hdn, "fc2").reshape(B, S, d), None

    x = params["tok_embed_weight"].astype(cd)[data.astype(jnp.int32)] \
        + params["pos_embed_weight"].astype(cd)[None, :S]
    # the layers are alike, so they are a scan over their stacked tensors:
    # one block is traced and compiled, not twenty-four; only one block's
    # activations at a time are kept for the backward pass
    parts = sorted(n[len("layer0_"):] for n in params
                   if n.startswith("layer0_"))
    stacked = {part: jnp.stack([params["layer%d_%s" % (i, part)]
                                for i in range(z["L"])]) for part in parts}
    x, _ = jax.lax.scan(jax.checkpoint(block), x, stacked)
    x = layer_norm(x, params["final_ln_gamma"],
                   params["final_ln_beta"]).reshape(B * S, d)
    logits = x @ params["lm_head_weight"].astype(cd).T \
        + params["lm_head_bias"].astype(cd)
    return jax.nn.softmax(logits.astype(f32), axis=-1)


def reference(cfg, traffic, params, data, label, compute_dtype, sample):
    """(mean token loss, sampled probabilities, {tensor: delta of one
    SGD-momentum step}) from float32 master ``params``; plain jax.numpy,
    nothing of ``mxnet_tpu``.  The objective is the token cross-entropy
    summed over each sequence and averaged over the batch (MXNet: summed
    head gradient, rescale_grad = 1/batch), so S times the mean loss."""
    import jax
    import jax.numpy as jnp
    from chipbench.common import sgd_momentum_delta
    cd = jnp.dtype(compute_dtype)
    z = _sizes(cfg, traffic)

    def objective(p, data, label, sample):
        probs = _forward(cfg, traffic, p, data, cd)
        lab = label.astype(jnp.int32).reshape(-1)
        picked = jnp.take_along_axis(probs, lab[:, None], axis=1)[:, 0]
        loss = -jnp.mean(jnp.log(picked))
        return loss * jnp.float32(z["S"]), (loss, probs[sample])

    # the batch and the sample are arguments: a closed-over array would be
    # a constant of the program, and no other seed would find it cached
    @jax.jit
    def step(p, data, label, sample):
        with jax.default_matmul_precision("highest"):
            grads, (loss, out) = jax.grad(objective, has_aux=True)(
                p, data, label, sample)
        return loss, out, sgd_momentum_delta(p, grads, cfg["optimizer"])
    return step(params, data, label, jnp.asarray(sample, jnp.int32))


# -- operations the model requires -------------------------------------------
def model_flops(cfg, traffic):
    """Per step, forward + backward = 3 x forward.  Forward: 2 x tokens x
    the matmul parameters (12 d^2 a layer with d_ff = 4 d, and the d x V
    head), plus causal attention's two matmuls over the lower triangle
    (2 x S^2 x d a layer and sequence).  Embedding lookups, norms and
    elementwise work are not counted; the flash backward's recomputed
    scores are not counted."""
    z = _sizes(cfg, traffic)
    tokens = z["B"] * z["S"]
    per_layer = 4 * z["d"] * z["d"] + 2 * z["d"] * z["d_ff"]
    dense = 2.0 * tokens * (z["L"] * per_layer + z["d"] * z["V"])
    attn = z["L"] * z["B"] * 2.0 * z["S"] * z["S"] * z["d"]
    return 3.0 * (dense + attn)


def kernel_costs(cfg, traffic):
    """{kernel name prefix: {"flops", "bytes", "calls_per_step"}} for one
    call of each flash kernel at this cell's shapes: the matmuls each
    performs over the causal half of the S x S score matrix (forward 2;
    dQ kernel 3: scores again, dP, dQ; dK/dV kernel 4: scores again, dV,
    dP, dK), and the tensors each must read and write once (bf16 q, k, v,
    o, do, dq, dk, dv; float32 log-sum-exp and delta rows)."""
    z = _sizes(cfg, traffic)
    bh = z["B"] * z["H"]
    matmul = 2.0 * z["S"] * z["S"] * z["hd"] / 2.0 * bh   # one, causal half
    tensor = bh * z["S"] * z["hd"] * 2.0                  # one bf16 tensor
    row = bh * z["S"] * 4.0                               # one f32 row stat
    return {
        "flash_fwd": {"flops": 2 * matmul, "bytes": 4 * tensor + row,
                      "calls_per_step": z["L"]},
        "flash_bwd_dq": {"flops": 3 * matmul,
                         "bytes": 5 * tensor + 2 * row,
                         "calls_per_step": z["L"]},
        "flash_bwd_dkv": {"flops": 4 * matmul,
                          "bytes": 6 * tensor + 2 * row,
                          "calls_per_step": z["L"]},
    }
