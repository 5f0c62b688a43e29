"""Decoder-only transformer language model — the long-context flagship.

NEW model family relative to the reference (the transformer era postdates
MXNet 0.12; SURVEY.md §5.7 designates long-context as this framework's
new-capability track).  TPU-first by construction:

* attention runs the Pallas flash kernel (ops/attention.py — forward AND
  FA2 backward, O(S) memory), causal;
* all projections are FullyConnected over (B*S, d) so XLA tiles one big
  MXU matmul per projection instead of S small ones;
* pre-norm residual blocks; FFN gelu (default) or SwiGLU (ffn_type='swiglu'); positions learned (default) or rotary (pos_type='rope') (optionally MoE via _contrib_MoE for
  expert parallelism);
* drops into Module/SoftmaxOutput exactly like every other model here, so
  the fused donated train step, bf16 compute_dtype, tp/sp sharding rules
  and ring attention all apply unchanged.
"""
from .. import symbol as sym

import math


def _rope_inv_freq(hd, base):
    """(hd/2,) inverse frequencies base**(-2i/hd), as graph constants."""
    half = hd // 2
    idx = sym.arange(start=0, stop=half)
    return sym.exp(idx * (-2.0 * math.log(base) / hd))


def _rope_apply(t, cos, sin, hd):
    """Rotate (…, hd) pairs (GPT-NeoX half-split form): cos/sin must
    broadcast against t's leading dims with last dim hd/2."""
    half = hd // 2
    t1 = sym.slice_axis(t, axis=3, begin=0, end=half)
    t2 = sym.slice_axis(t, axis=3, begin=half, end=None)
    return sym.Concat(
        sym.broadcast_mul(t1, cos) - sym.broadcast_mul(t2, sin),
        sym.broadcast_mul(t2, cos) + sym.broadcast_mul(t1, sin), dim=3)


def _rope_tables(seq_len, hd, base):
    """(cos, sin) of ONE angle table shared by every layer (the decode
    graph does the same): (1, 1, S, hd/2).  Nodes no Variable reaches:
    under mixed precision they stay float32 (executor._amp_uncast_inputs)."""
    if hd % 2:
        raise ValueError(f"rope needs even head_dim, got {hd}")
    ang = sym.broadcast_mul(
        sym.Reshape(sym.arange(start=0, stop=seq_len),
                    shape=(1, 1, seq_len, 1)),
        sym.Reshape(_rope_inv_freq(hd, base), shape=(1, 1, 1, hd // 2)))
    return sym.cos(ang), sym.sin(ang)


def _dense(x, num_hidden, name, no_bias):
    # GPT-2's nodes carry no `no_bias` attr: their graph stays as it was
    kw = {"no_bias": True} if no_bias else {}
    return sym.FullyConnected(x, num_hidden=num_hidden, name=name, **kw)


def _attention_block(x, seq_len, d_model, num_heads, name,
                     num_kv_heads=None, causal=True, rope_cs=None,
                     no_bias=False):
    """x: (B, S, d) → (B, S, d) flash attention + projection (causal by
    default — the LM; causal=False gives the bidirectional encoder form
    ViT uses).

    ``num_kv_heads < num_heads`` = grouped-query attention (num_kv_heads=1
    is MQA): the QKV projection emits only num_kv_heads K/V heads and the
    flash kernel shares them per query-head group without materializing
    repeats — smaller KV projection params and KV cache."""
    h = num_heads
    hk = h if num_kv_heads is None else num_kv_heads
    if hk < 1 or h % hk:
        raise ValueError(f"num_heads {h} not divisible by kv heads {hk}")
    if d_model % h:
        raise ValueError(
            f"d_model {d_model} not divisible by num_heads {h}")
    hd = d_model // h
    flat = sym.Reshape(x, shape=(-1, d_model))
    qkv = _dense(flat, (h + 2 * hk) * hd, f"{name}_qkv", no_bias)
    q = sym.slice_axis(qkv, axis=1, begin=0, end=h * hd)
    k = sym.slice_axis(qkv, axis=1, begin=h * hd, end=(h + hk) * hd)
    v = sym.slice_axis(qkv, axis=1, begin=(h + hk) * hd,
                       end=(h + 2 * hk) * hd)

    def heads(t, nh):
        t = sym.Reshape(t, shape=(-1, seq_len, nh, hd))
        return sym.transpose(t, axes=(0, 2, 1, 3))    # (B, nh, S, hd)

    qh, kh = heads(q, h), heads(k, hk)
    if rope_cs is not None:
        cos, sin = rope_cs
        qh = _rope_apply(qh, cos, sin, hd)
        kh = _rope_apply(kh, cos, sin, hd)
    attn = sym.contrib.FlashAttention(qh, kh,
                                      heads(v, hk), causal=causal,
                                      name=f"{name}_flash")
    attn = sym.transpose(attn, axes=(0, 2, 1, 3))     # (B, S, H, hd)
    attn = sym.Reshape(attn, shape=(-1, d_model))
    out = _dense(attn, d_model, f"{name}_proj", no_bias)
    return sym.Reshape(out, shape=(-1, seq_len, d_model))


def _ffn_block(x, seq_len, d_model, d_ff, name, moe_experts=0, moe_k=1,
               ffn_type="gelu", no_bias=False):
    flat = sym.Reshape(x, shape=(-1, d_model))
    if ffn_type == "swiglu" and moe_experts:
        raise ValueError(
            "ffn_type='swiglu' with moe_experts>0 is not supported — "
            "the MoE expert FFN is gelu; drop one of the two options")
    if ffn_type == "swiglu":
        # SwiGLU (Shazeer 2020): silu(xW1) * xW3 -> W2.  One fused
        # projection emits both halves so the MXU sees a single matmul.
        both = _dense(flat, 2 * d_ff, f"{name}_fc1", no_bias)  # [gate | lin]
        gate = sym.slice_axis(both, axis=1, begin=0, end=d_ff)
        lin = sym.slice_axis(both, axis=1, begin=d_ff, end=None)
        hdn = gate * sym.sigmoid(gate) * lin
        out = _dense(hdn, d_model, f"{name}_fc2", no_bias)
        return sym.Reshape(out, shape=(-1, seq_len, d_model))
    if ffn_type not in ("gelu", "swiglu"):
        raise ValueError(f"ffn_type must be gelu|swiglu, got {ffn_type!r}")
    if moe_experts and no_bias:
        raise ValueError("no_bias with moe_experts>0 is not supported — "
                         "the MoE expert FFN has biases")
    if moe_experts:
        gate = sym.Variable(f"{name}_gate_weight",
                            shape=(d_model, moe_experts))
        w1 = sym.Variable(f"{name}_expert_w1_weight",
                          shape=(moe_experts, d_model, d_ff))
        b1 = sym.Variable(f"{name}_expert_b1_bias", shape=(moe_experts, d_ff))
        w2 = sym.Variable(f"{name}_expert_w2_weight",
                          shape=(moe_experts, d_ff, d_model))
        b2 = sym.Variable(f"{name}_expert_b2_bias",
                          shape=(moe_experts, d_model))
        out = sym.contrib.MoE(flat, gate, w1, b1, w2, b2,
                              num_experts=moe_experts, k=moe_k,
                              activation="gelu", name=f"{name}_moe")
    else:
        hdn = _dense(flat, d_ff, f"{name}_fc1", no_bias)
        hdn = hdn * sym.sigmoid(hdn * 1.702)   # gelu (sigmoid approx)
        out = _dense(hdn, d_model, f"{name}_fc2", no_bias)
    return sym.Reshape(out, shape=(-1, seq_len, d_model))


def transformer_lm(vocab_size, seq_len, num_layers=2, d_model=128,
                   num_heads=4, num_kv_heads=None, d_ff=None,
                   moe_experts=0, moe_k=1, max_len=None,
                   pos_type="learned", rope_base=10000.0,
                   ffn_type="gelu", loss_type="softmax", ce_chunks=8):
    """Causal LM train symbol: data (B, S) token ids,
    softmax_label (B, S) next-token ids.

    ``max_len`` (default seq_len) sizes the positional embedding; pass
    the largest bucket when building per-bucket symbols for
    BucketingModule so all buckets share ONE pos_embed parameter.

    ``loss_type="chunked_ce"`` replaces the SoftmaxOutput head with the
    chunked LM loss (``ce_chunks`` vocab chunks): peak memory for the
    head drops from O(B*S*V) to O(B*S*V/ce_chunks), the output becomes
    the scalar mean CE loss (track it with the ``Loss`` metric;
    perplexity = exp(loss)), and lm_head parameter names are unchanged
    so checkpoints swap between the two heads."""
    d_ff = d_ff or 4 * d_model
    max_len = max_len or seq_len
    if max_len < seq_len:
        raise ValueError(
            f"transformer_lm: max_len ({max_len}) must be >= seq_len "
            f"({seq_len}) — pass the largest bucket as max_len")
    if pos_type not in ("learned", "rope"):
        raise ValueError(f"pos_type must be learned|rope, got {pos_type!r}")
    if loss_type not in ("softmax", "chunked_ce"):
        raise ValueError(
            f"loss_type must be softmax|chunked_ce, got {loss_type!r}")
    if loss_type == "chunked_ce" and int(ce_chunks) < 1:
        raise ValueError(f"ce_chunks must be >= 1, got {ce_chunks}")
    data = sym.Variable("data")
    x = sym.Embedding(data, input_dim=vocab_size, output_dim=d_model,
                      name="tok_embed")
    if pos_type == "learned":
        # named *_weight so default initializers recognize it
        pos = sym.Variable("pos_embed_weight", shape=(max_len, d_model))
        pos = sym.slice_axis(pos, axis=0, begin=0, end=seq_len)
        x = sym.broadcast_add(x, sym.expand_dims(pos, axis=0))
    rope_cs = None
    if pos_type == "rope":
        rope_cs = _rope_tables(seq_len, d_model // num_heads, rope_base)
    for i in range(num_layers):
        name = f"layer{i}"
        a = _attention_block(sym.LayerNorm(x, name=f"{name}_ln1"),
                             seq_len, d_model, num_heads, name,
                             num_kv_heads=num_kv_heads,
                             rope_cs=rope_cs)
        x = x + a
        f = _ffn_block(sym.LayerNorm(x, name=f"{name}_ln2"),
                       seq_len, d_model, d_ff, name,
                       moe_experts=moe_experts, moe_k=moe_k,
                       ffn_type=ffn_type)
        x = x + f
    x = sym.LayerNorm(x, name="final_ln")
    hidden = sym.Reshape(x, shape=(-1, d_model))
    label = sym.Reshape(sym.Variable("softmax_label"), shape=(-1,))
    if loss_type == "chunked_ce":
        # memory-lean head for big vocab / long context: the (N, V)
        # logits never materialize (ops/chunked_loss.py).  Param names
        # match FullyConnected's, so checkpoints swap between heads.
        # standard initializers key on the names: *_weight random,
        # *_bias zero — same as FullyConnected's implicit params
        w = sym.Variable("lm_head_weight", shape=(vocab_size, d_model))
        b = sym.Variable("lm_head_bias", shape=(vocab_size,))
        tok_loss = sym.chunked_lm_loss(hidden, w, b, label,
                                       num_chunks=ce_chunks)
        # output IS the mean loss (use the Loss metric; exp(loss) = ppl)
        return sym.make_loss(sym.mean(tok_loss))
    logits = sym.FullyConnected(hidden, num_hidden=vocab_size,
                                name="lm_head")
    return sym.SoftmaxOutput(data=logits, label=label, name="softmax")


def _sandwich_layer(x, seq_len, d_model, num_heads, d_ff, name,
                    num_kv_heads=None, rope_cs=None, norm_eps=1e-6):
    """One bias-free decoder layer with a norm before AND after each
    sub-block, four gains a layer: ``a = x + N2(Attn(N1(x)))``,
    ``y = a + N4(FFN(N3(a)))``, N = RMSNorm, FFN = SwiGLU."""
    def norm(t, which):
        return sym.RMSNorm(t, eps=norm_eps, name=f"{name}_{which}")

    a = _attention_block(norm(x, "ln1"), seq_len, d_model, num_heads, name,
                         num_kv_heads=num_kv_heads, rope_cs=rope_cs,
                         no_bias=True)
    x = x + norm(a, "ln1_post")
    f = _ffn_block(norm(x, "ln2"), seq_len, d_model, d_ff, name,
                   ffn_type="swiglu", no_bias=True)
    return x + norm(f, "ln2_post")


def looped_transformer_lm(vocab_size, seq_len, num_layers=2, d_model=128,
                          num_heads=4, num_kv_heads=None, d_ff=None,
                          loop_steps=4, rope_base=1e6, norm_eps=1e-6,
                          exit_beta=0.05, ce_chunks=8):
    """Looped causal LM train symbol (Ouro; Zhu et al. 2025, "Scaling
    Latent Reasoning via Looped Language Models"): the whole stack of
    ``num_layers`` sandwich-normed, bias-free RoPE / SwiGLU layers and the
    final RMSNorm is run ``loop_steps`` times over its own output with the
    SAME weights -- one loop node (``sym.contrib.foreach``) whose body is
    traced once and rematerialised one loop step at a time in the backward
    pass (``remat=True``: sixteen layer applications' activations at 4096
    tokens do not fit a chip beside the parameters; kept of each step are
    the flash kernel's output and log-sum-exp and the outputs of ``proj``
    and ``fc2``, the matmuls whose contraction is at least their width,
    the rest is made again).  An untied head reads every loop step's
    state, an exit gate ``sigmoid(h . w + b)`` gives each token a
    distribution over the step to stop at, and the objective is the
    expected cross-entropy under it plus ``exit_beta`` times its negative
    entropy (``_contrib_ExpectedExitLoss``), per token.

    data (B, S) token ids, softmax_label (B, S) next-token ids.  Outputs:
    [0] ``softmax`` of the last loop step's logits, (B*S, V),
    gradient-blocked: what is served with the exit threshold at 1 and what
    a metric reads; [1] the per-token objective (B*S,) as a ``MakeLoss``
    head (Module's ``rescale_grad`` = 1/batch makes the step that of the
    token sum averaged over the batch, as ``transformer_lm``'s).  The
    per-step cross-entropies go through ``chunked_lm_loss``: no step's
    (B*S, V) logits are kept for the backward pass."""
    d_ff = d_ff or 4 * d_model
    if int(loop_steps) < 1:
        raise ValueError(f"loop_steps must be >= 1, got {loop_steps}")
    steps = int(loop_steps)
    data = sym.Variable("data")
    x = sym.Embedding(data, input_dim=vocab_size, output_dim=d_model,
                      name="tok_embed")

    def stack(_, h):
        # inside the body: a body may use no computed Symbol from outside
        rope_cs = _rope_tables(seq_len, d_model // num_heads, rope_base)
        for i in range(num_layers):
            h = _sandwich_layer(h, seq_len, d_model, num_heads, d_ff,
                                f"layer{i}", num_kv_heads=num_kv_heads,
                                rope_cs=rope_cs, norm_eps=norm_eps)
        h = sym.RMSNorm(h, eps=norm_eps, name="final_norm")
        return h, h     # every step's state is read, and feeds the next

    states, _ = sym.contrib.foreach(stack, None, x, num_iter=steps,
                                    remat=True, name="loop")   # (T,B,S,d)
    flat = sym.Reshape(states, shape=(-1, d_model))           # step-major
    label = sym.Reshape(sym.Variable("softmax_label"), shape=(-1,))
    head = sym.Variable("lm_head_weight", shape=(vocab_size, d_model))
    ce = sym.chunked_lm_loss(flat, head, sym.zeros((vocab_size,)),
                             sym.tile(label, reps=(steps,)),
                             num_chunks=ce_chunks, name="step_ce")
    gate = sym.FullyConnected(flat, num_hidden=1, name="exit_gate")
    objective = sym.contrib.ExpectedExitLoss(gate, ce, steps=steps,
                                             beta=exit_beta,
                                             name="exit_loss")[0]
    last = sym.BlockGrad(sym.Reshape(
        sym.slice_axis(states, axis=0, begin=steps - 1, end=steps),
        shape=(-1, d_model)))
    logits = sym.FullyConnected(last, weight=head, num_hidden=vocab_size,
                                no_bias=True, name="lm_head")
    probs = sym.BlockGrad(sym.softmax(logits, axis=-1), name="softmax")
    return sym.Group([probs, sym.MakeLoss(objective, name="objective")])


def get_symbol(vocab_size=1000, seq_len=128, **kwargs):
    return transformer_lm(vocab_size, seq_len, **kwargs)


def transformer_decode_step(vocab_size, max_len, batch_size,
                            num_layers=2, d_model=128,
                            num_heads=4, num_kv_heads=None, d_ff=None,
                            moe_experts=0, moe_k=1,
                            pos_type="learned", rope_base=10000.0,
                            ffn_type="gelu"):
    """One autoregressive decode step with a rolled KV cache.

    Parameter names match ``transformer_lm`` exactly (pass the SAME
    moe_experts/moe_k used in training — MoE checkpoints carry expert
    params, dense ones carry fc1/fc2), so trained weights load straight
    into this one.  The cache is carried
    through Module state_names (set_states/get_states): per layer
    ``layer{i}_k_cache``/``layer{i}_v_cache`` of shape
    (batch_size, kv_heads, max_len, head_dim), plus ``cur_pos`` — the cache
    ROLLS left one slot per step (static shapes; validity is a mask
    computed from cur_pos, so jit never sees a dynamic shape).

    Generation length is bounded by ``max_len``.  With
    ``pos_type="learned"`` absolute positions feed the embedding lookup,
    so decoding past max_len silently clamps to the last position.  With
    ``pos_type="rope"`` the rolled cache instead becomes a SLIDING
    window past max_len: the oldest tokens drop out of attention while
    rotation angles keep growing beyond anything seen in training —
    different failure mode, same sizing rule: keep prompt+generated
    tokens within max_len (generate_lm.py enforces this).

    Inputs: data (B,) current token ids.  Outputs:
    [logits (B, vocab)] + [new k/v caches per layer] + [cur_pos + 1].
    """
    d_ff = d_ff or 4 * d_model
    h = num_heads
    hk = h if num_kv_heads is None else num_kv_heads
    if hk < 1 or h % hk:
        raise ValueError(f"num_heads {h} not divisible by kv heads {hk}")
    hd = d_model // h
    g = h // hk

    B = int(batch_size)  # decode graphs pin the batch (standard for
    # KV-cache inference: the cache shape IS the signature)
    data = sym.Variable("data")            # (B,) token ids
    pos = sym.Variable("cur_pos", shape=(B,))   # float position index
    x = sym.Embedding(data, input_dim=vocab_size, output_dim=d_model,
                      name="tok_embed")    # (B, d)
    if pos_type == "learned":
        pos_w = sym.Variable("pos_embed_weight", shape=(max_len, d_model))
        pv = sym.Embedding(pos, weight=pos_w, input_dim=max_len,
                           output_dim=d_model, name="pos_lookup")
        x = x + pv
    elif pos_type != "rope":
        raise ValueError(f"pos_type must be learned|rope, got {pos_type!r}")
    if pos_type == "rope":
        if hd % 2:
            raise ValueError(f"rope needs even head_dim, got {hd}")
        # rotation angles for the CURRENT absolute position, per batch
        # row: (B, 1, 1, hd/2).  Cached K entries were rotated at THEIR
        # positions when inserted, so the rolled cache needs no rework —
        # scores depend only on relative angles.
        rope_inv = _rope_inv_freq(hd, rope_base)
        rope_ang = sym.broadcast_mul(
            sym.Reshape(pos, shape=(-1, 1, 1, 1)),
            sym.Reshape(rope_inv, shape=(1, 1, 1, hd // 2)))
        rope_cos, rope_sin = sym.cos(rope_ang), sym.sin(rope_ang)

    # cache slot i holds the token at absolute position cur_pos-(L-1-i);
    # slot valid iff i >= max_len - 1 - cur_pos
    slot = sym.Reshape(sym.arange(start=0, stop=max_len),
                       shape=(1, max_len))
    valid = sym.broadcast_greater_equal(
        slot, sym.Reshape(float(max_len) - 1.0 - pos, shape=(-1, 1)))
    # (B, max_len) 1.0 where the cache slot is a real token (the current
    # token lands in the LAST slot this step)
    new_states = []
    scale = 1.0 / (hd ** 0.5)
    for i in range(num_layers):
        name = f"layer{i}"
        xin = sym.LayerNorm(x, name=f"{name}_ln1")
        qkv = sym.FullyConnected(xin, num_hidden=(h + 2 * hk) * hd,
                                 name=f"{name}_qkv")
        q = sym.Reshape(sym.slice_axis(qkv, axis=1, begin=0, end=h * hd),
                        shape=(-1, h, 1, hd))
        kn = sym.Reshape(sym.slice_axis(qkv, axis=1, begin=h * hd,
                                        end=(h + hk) * hd),
                         shape=(-1, hk, 1, hd))
        vn = sym.Reshape(sym.slice_axis(qkv, axis=1, begin=(h + hk) * hd,
                                        end=(h + 2 * hk) * hd),
                         shape=(-1, hk, 1, hd))
        if pos_type == "rope":
            q = _rope_apply(q, rope_cos, rope_sin, hd)
            kn = _rope_apply(kn, rope_cos, rope_sin, hd)
        kc = sym.Variable(f"{name}_k_cache",
                          shape=(B, hk, max_len, hd))
        vc = sym.Variable(f"{name}_v_cache",
                          shape=(B, hk, max_len, hd))
        kc2 = sym.Concat(sym.slice_axis(kc, axis=2, begin=1, end=None),
                         kn, dim=2, name=f"{name}_kroll")
        vc2 = sym.Concat(sym.slice_axis(vc, axis=2, begin=1, end=None),
                         vn, dim=2, name=f"{name}_vroll")
        new_states += [kc2, vc2]
        # GQA: repeat cached kv heads per query group for the score matmul
        kr = sym.repeat(kc2, repeats=g, axis=1) if g > 1 else kc2
        vr = sym.repeat(vc2, repeats=g, axis=1) if g > 1 else vc2
        # scores (B, h, 1, max_len) = q · k^T
        qf = sym.Reshape(q, shape=(-3, 1, hd))        # (B*h, 1, hd)
        kf = sym.Reshape(kr, shape=(-3, max_len, hd))
        s = sym.batch_dot(qf, sym.swapaxes(kf, dim1=1, dim2=2)) * scale
        s = sym.Reshape(s, shape=(-4, -1, h, max_len))  # (B, h, max_len)
        # additive mask: valid is 1.0/0.0, so (valid-1)*1e30 is 0 on real
        # slots and -1e30 on empty cache slots
        mask = sym.Reshape((valid - 1.0) * 1e30,
                           shape=(-4, -1, 1, max_len))
        s = sym.broadcast_add(s, mask)
        p = sym.softmax(s, axis=-1)
        pf = sym.Reshape(p, shape=(-3, 1, max_len))   # (B*h, 1, L)
        vf = sym.Reshape(vr, shape=(-3, max_len, hd))
        o = sym.batch_dot(pf, vf)                     # (B*h, 1, hd)
        o = sym.Reshape(o, shape=(-4, -1, h, hd))
        o = sym.Reshape(o, shape=(-1, d_model))
        a = sym.FullyConnected(o, num_hidden=d_model, name=f"{name}_proj")
        x = x + a
        f = _ffn_block(sym.expand_dims(
            sym.LayerNorm(x, name=f"{name}_ln2"), axis=1),
            1, d_model, d_ff, name,
            moe_experts=moe_experts, moe_k=moe_k, ffn_type=ffn_type)
        x = x + sym.Reshape(f, shape=(-1, d_model))
    x = sym.LayerNorm(x, name="final_ln")
    logits = sym.FullyConnected(x, num_hidden=vocab_size, name="lm_head")
    new_states.append(pos + 1.0)
    return sym.Group([logits] + new_states)
