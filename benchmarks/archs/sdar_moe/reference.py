"""The plain reference of SDAR-MoE (`sdar_moe`), from the equations:
float32 `jax.numpy`, matmul precision `highest`, no cache, no pages, no
grouped matmul, no batching, a full [T, T] mask.  It shares no code with
`ray_tpu.models` and imports jax alone.

It takes the SAME weights the program serves (bf16 values, the program's
layout: a tuple of layers) and upcasts a matrix, or one expert, at a
time: attention is computed in blocks of queries against ALL keys under
the full mask, the experts one by one under a mask, the output head a
block of positions at a time (`head`), so ~2,000 positions fit beside a
serving replica's weights and cache.

The equations (`c` is the configuration file's dict; x is the residual
stream [T, hidden_size]; RMSNorm eps `rms_norm_eps`; B =
`assumed.block_length`), for ANY tokens, mask ids among them:

  block (pre-norm):  h = x + Attn(rms(x)),  y = h + MoE(rms(h)).

  projections, no biases: q = W_q u as `num_attention_heads` heads of
  `head_dim`; k = W_k u, v = W_v u as `num_key_value_heads` heads.  q
  and k each pass an RMSNorm over the `head_dim` of a head (one weight
  vector a layer for q, one for k, shared by the heads), then RoPE over
  the whole head, pairs (i, i + head_dim / 2), theta `rope_theta`,
  absolute positions.

  scores s_pj = q_p . k_j / sqrt(head_dim); query head h reads key-value
  head h // (heads / kv heads).  THE MASK: the query at position p sees
  the key at position j iff floor(j / B) <= floor(p / B): causal between
  blocks of B positions, full inside one.  A plain softmax.

  expert layer (every layer): p = softmax(u W_r) over all `num_experts`
  in float32; the `num_experts_per_tok` largest are chosen; a chosen
  expert's weight is p_e over the sum of the chosen (`norm_topk_prob`).
  The layer adds the sum over chosen experts of weight x SwiGLU_e(u).
  No shared expert.

  head: rms, then W_head (untied).  THE LOGITS AT POSITION i ARE OF
  TOKEN i ITSELF: nothing is shifted; a masked position is predicted
  from the mask token's embedding standing in it.

`generate` is the family's block generation over this forward, as plain
as it comes (one full forward a step): the loop the engine's stream is
held equal to.

`c` may carry switches that only tools/sdar_limits.py and the tests
write (`_causal_in_block`, `_shift_logits`, `_top_k`, `_no_renorm`,
`_no_qk_norm`): the controls a comparison must catch.
"""

from __future__ import annotations


def block_length(c) -> int:
    return int(c["assumed"]["block_length"])


def moe(h, lp, c, lo=lambda a: a):
    """The expert layer on normed h [T, D] float32: each expert applied
    to every token under a mask."""
    import jax
    import jax.numpy as jnp
    from jax import lax

    f32 = jnp.float32
    E = c["num_experts"]
    p = jax.nn.softmax(h @ lp["router"].astype(f32), axis=-1)
    if p.shape[1] != E:
        raise ValueError(f"the router scores {p.shape[1]} experts, the "
                         f"published count is {E}")
    w, ids = lax.top_k(p, c.get("_top_k", c["num_experts_per_tok"]))
    if c["norm_topk_prob"] and not c.get("_no_renorm"):
        w = w / w.sum(-1, keepdims=True)
    # weight of expert e for each token (0 where not chosen)
    dense_w = jnp.zeros((h.shape[0], E), f32).at[
        jnp.arange(h.shape[0])[:, None], ids].add(w)
    ex = lp["experts"]

    def one(e, acc):
        gate, up, down = (lo(ex[n][e].astype(f32))
                          for n in ("w_gate", "w_up", "w_down"))
        mid = jax.nn.silu(lo(h) @ gate) * (lo(h) @ up)
        return acc + lax.dynamic_slice_in_dim(dense_w, e, 1, 1) \
            * (lo(mid) @ down)
    return lax.fori_loop(0, ex["w_gate"].shape[0], one, jnp.zeros_like(h))


def _lo(round_to):
    import jax.numpy as jnp

    def lo(a):
        a = a.astype(jnp.float32)
        if round_to is None:
            return a
        # a saturating cast: an 8-bit float has no infinity
        top = float(jnp.finfo(round_to).max)
        return jnp.clip(a, -top, top).astype(round_to).astype(jnp.float32)
    return lo


def hidden(params, tokens, c, query_block=128, round_to=None):
    """tokens [T] int32 -> the last norm's output [T, hidden] float32:
    what `head` takes.  `round_to` (a dtype name) rounds both inputs of
    every weight matmul to that type first: the reference in a lower
    precision, for setting the comparison's limits
    (tools/sdar_limits.py), never for a judged run."""
    import jax
    import jax.numpy as jnp
    from jax import lax

    f32 = jnp.float32
    H, G, Dh = (c["num_attention_heads"], c["num_key_value_heads"],
                c["head_dim"])
    B = block_length(c)
    eps = float(c["rms_norm_eps"])
    theta = float(c["rope_theta"])
    T = tokens.shape[0]
    qb = min(query_block, T)
    n_qb = -(-T // qb)
    Tp = n_qb * qb
    positions = jnp.arange(T)
    lo = _lo(round_to)

    def rms(x, w):
        return x * lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) \
            * w.astype(f32)

    def rope(x):                                   # [T, heads, Dh]
        half = Dh // 2
        freqs = theta ** (-jnp.arange(half, dtype=f32) / half)
        ang = positions.astype(f32)[:, None, None] * freqs[None, None, :]
        cos, sin = jnp.cos(ang), jnp.sin(ang)
        a, b = x[..., :half], x[..., half:]
        return jnp.concatenate([a * cos - b * sin, a * sin + b * cos], -1)

    def attention(x, lp):
        u = rms(x, lp["ln1"])
        wkv = lp["wkv"]
        q = jnp.einsum("td,dhk->thk", lo(u), lo(lp["wq"]))
        k = jnp.einsum("td,dgk->tgk", lo(u), lo(wkv[:, 0]))
        v = jnp.einsum("td,dgk->tgk", lo(u), lo(wkv[:, 1]))
        if k.shape[1] != G or q.shape[1] != H:
            raise ValueError(f"{q.shape[1]} query heads on {k.shape[1]} "
                             f"key-value heads")
        if not c.get("_no_qk_norm"):
            q, k = rms(q, lp["qn"]), rms(k, lp["kn"])
        q, k = rope(q), rope(k)

        def attend(qq, pp):                        # [qb, H, Dh], [qb]
            qg = qq.reshape(qb, G, H // G, Dh)
            s = jnp.einsum("qgrd,sgd->qgrs", qg, k) * Dh ** -0.5
            if c.get("_causal_in_block"):
                seen = positions[None, :] <= pp[:, None]
            else:
                seen = positions[None, :] // B <= pp[:, None] // B
            s = jnp.where(seen[:, None, None, :], s, -jnp.inf)
            return jnp.einsum("qgrs,sgd->qgrd", jax.nn.softmax(s, -1), v)

        cut = [jnp.pad(a, [(0, Tp - T)] + [(0, 0)] * (a.ndim - 1)
                       ).reshape((n_qb, qb) + a.shape[1:])
               for a in (q, positions)]
        o = lax.map(lambda args: attend(*args), tuple(cut))
        o = o.reshape(Tp, H, Dh)[:T]
        return x + jnp.einsum("thk,hkd->td", lo(o), lo(lp["wo"]))

    with jax.default_matmul_precision("highest"):
        x = jnp.take(params["wte"], tokens, axis=0).astype(f32)
        for lp in params["layers"]:
            x = attention(x, lp)
            x = x + moe(rms(x, lp["ln2"]), lp, c, lo)
        x = rms(x, params["ln_f"])
    if c.get("_shift_logits"):
        # the control: position i answers for token i + 1
        x = jnp.roll(x, 1, axis=0)
    return x


def head(params, x, round_to=None, width_blocks=8):
    """x [n, hidden] float32 (rows of `hidden`) -> logits [n, V], the
    head's columns upcast a block at a time."""
    import jax
    import jax.numpy as jnp
    from jax import lax

    lo = _lo(round_to)
    wlm = params["wlm"]
    V = wlm.shape[1]
    n = width_blocks if V % width_blocks == 0 else 1
    w = V // n

    def block(i, out):
        cols = lo(lax.dynamic_slice_in_dim(wlm, i * w, w, 1))
        return lax.dynamic_update_slice_in_dim(out, lo(x) @ cols, i * w, 1)
    with jax.default_matmul_precision("highest"):
        return lax.fori_loop(0, n, block,
                             jnp.zeros((x.shape[0], V), jnp.float32))


def forward(params, tokens, c, query_block=128, round_to=None):
    """tokens [T] int32 -> logits [T, V] float32."""
    return head(params, hidden(params, tokens, c, query_block, round_to),
                round_to)


def generate(params, prompt, max_new, c, logits_of=None):
    """The family's block generation (`assumed`: B positions a block,
    B denoising steps, static low-confidence remasking, greedy): the
    prompt's last `L mod B` tokens open the first block as fixed
    positions, every other position of a block starts as the mask token;
    a step is one forward of everything so far, and of the positions
    still masked the one whose largest softmax probability is highest is
    fixed to its argmax; blocks follow until `max_new` tokens stand.
    Masked-ness is a bool a position, never a comparison of ids.
    `logits_of(tokens) -> [T, V]` defaults to this file's `forward`.
    Returns the `max_new` generated tokens."""
    import jax
    import jax.numpy as jnp

    B = block_length(c)
    mask_id = int(c["assumed"]["mask_token_id"])
    if logits_of is None:
        fwd = jax.jit(lambda p, t: forward(p, t, c))
        logits_of = lambda toks: fwd(  # noqa: E731
            params, jnp.asarray(toks, jnp.int32))
    seq = [int(t) for t in prompt]
    L = len(seq)
    while len(seq) < L + max_new:
        start = len(seq) // B * B
        masked = [False] * (len(seq) - start) + [True] * (start + B
                                                          - len(seq))
        seq = seq + [mask_id] * (start + B - len(seq))
        while any(masked):
            z = jnp.asarray(logits_of(seq))[start:start + B]
            # the largest softmax probability of each position
            prob = jnp.exp(z.max(-1) - jax.nn.logsumexp(z, axis=-1))
            at = int(jnp.argmax(jnp.where(jnp.asarray(masked), prob, -1.0)))
            seq[start + at] = int(jnp.argmax(z[at]))
            masked[at] = False
    return seq[L:L + max_new]
