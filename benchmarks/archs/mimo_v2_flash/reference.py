"""The plain reference of MiMo-V2-Flash (`mimo_v2_flash`), from the
equations: float32 `jax.numpy`, matmul precision `highest`, no cache, no
pages, no rings, no grouped matmul, no batching.  It shares no code with
`ray_tpu.models` and imports jax alone.

It takes the SAME weights the program serves (bf16 values, the program's
layout: a tuple of layers) and upcasts a matrix, or one expert, at a
time: attention is computed in blocks of queries against ALL keys under
a full mask, the dense feed-forward and the output head in blocks of
columns, the experts one by one, so ~2,000 positions fit beside a
serving replica's weights and cache.

The equations (`c` is the configuration file's dict; x is the residual
stream [T, hidden_size]; RMSNorm eps `layernorm_epsilon`; the kind of
layer l is `hybrid_layer_pattern[l]`: 0 full, 1 window):

  block (pre-norm):  h = x + Attn_kind(rms(x)),  y = h + FF_l(rms(h)).

  projections, no biases: q = W_q u as `num_attention_heads` heads of
  `head_dim` (192); k = W_k u as G heads of 192; v =
  `attention_value_scale` x (W_v u) as G heads of `v_head_dim` (128);
  G = `num_key_value_heads` in a full layer, `swa_num_key_value_heads`
  in a window layer.  RoPE turns the first int(`partial_rotary_factor`
  x 192) = 64 dimensions of every head of q and k, pairs (i, i + 32),
  and leaves the other 128; theta `rope_theta` in a full layer,
  `swa_rope_theta` in a window layer.

  scores s_tj = q_t . k_j / sqrt(192); query head h reads key-value head
  h // (heads / G).  Full layer: j <= t, a plain softmax.  Window layer:
  0 <= t - j < `sliding_window`, and a learned per-head SINK b_h that is
  one more column of the scores, in the softmax and dropped after it:
  p_tj = exp(s_tj - m) / (exp(b_h - m) + sum_j' exp(s_tj' - m)).  The
  sink has no value, so a head's weights sum to less than 1.
  out_t = sum_j p_tj v_j; Attn = W_o concat(heads).

  feed-forward: SwiGLU of `intermediate_size` in layer 0
  (`moe_layer_freq`).  After it: s = sigmoid(u W_r) over ALL published
  experts in float32; the `num_experts_per_tok` largest of s + b are
  chosen (b: the selection bias of `noaux_tc`); a chosen expert's weight
  is s_i / (sum of s over the chosen + 1e-20), times
  `routed_scaling_factor` (null: 1).  The layer adds the sum over chosen
  experts of weight x SwiGLU_e(u) — OVER THE EXPERTS HELD HERE ONLY:
  experts `expert_offset` .. `expert_offset + n_routed_experts - 1` of
  the published count.  What the others would add is left out, as in
  the program.  There is no shared expert.

  head: rms, then W_head (untied).  The multi-token-prediction layers
  are not part of these logits and are not here.

`c` may carry switches that only tools/mimo_limits.py and the tests
write (`_no_sink`, `_no_v_scale`, `_thetas_swapped`, `_rotary_dim`,
`_window`, `_top_k`): the controls a comparison must catch.
"""

from __future__ import annotations


def chosen_experts(s, bias, c):
    """s [N, E] float32 sigmoid scores -> (ids [N, k], weights [N, k])."""
    import jax.numpy as jnp
    from jax import lax

    k = c.get("_top_k", c["num_experts_per_tok"])
    ids = lax.top_k(s + bias[None, :], k)[1]
    w = jnp.take_along_axis(s, ids, axis=1)
    w = w / (w.sum(-1, keepdims=True) + 1e-20)
    return ids, w * (c["routed_scaling_factor"] or 1.0)


def moe(h, lp, c, lo=lambda a: a, with_routes=False):
    """The expert layer on normed h [T, D] float32: the held experts'
    part, each held expert applied to every token under a mask.  `lp`
    the layer's weights; the held experts are the published ones
    `expert_offset` .. + `n_routed_experts`."""
    import jax
    import jax.numpy as jnp
    from jax import lax

    f32 = jnp.float32
    E_all = c["published"]["n_routed_experts"]
    held, first = c["n_routed_experts"], c.get("expert_offset", 0)
    s = jax.nn.sigmoid(h @ lp["router"].astype(f32))
    if s.shape[1] != E_all:
        raise ValueError(f"the router scores {s.shape[1]} experts, the "
                         f"published count is {E_all}")
    ids, w = chosen_experts(s, lp["router_bias"].astype(f32), c)
    # weight of published expert e for each token (0 where not chosen)
    dense_w = jnp.zeros((h.shape[0], E_all), f32).at[
        jnp.arange(h.shape[0])[:, None], ids].add(w)
    ex = lp["experts"]

    def one(e, acc):
        gate, up, down = (lo(ex[n][e].astype(f32))
                          for n in ("w_gate", "w_up", "w_down"))
        mid = jax.nn.silu(lo(h) @ gate) * (lo(h) @ up)
        return acc + lax.dynamic_slice_in_dim(dense_w, first + e, 1, 1) \
            * (lo(mid) @ down)
    out = lax.fori_loop(0, held, one, jnp.zeros_like(h))
    return (out, ids) if with_routes else out


def forward(params, tokens, c, query_block=128, width_blocks=8,
            round_to=None, with_routes=False):
    """tokens [T] int32 -> logits [T, V] float32.  `round_to` (a dtype
    name, e.g. "bfloat16") rounds both inputs of every weight matmul to
    that type first: the reference in a lower precision, for setting the
    comparison's limits (tools/mimo_limits.py), never for a judged run.
    `with_routes` also returns the chosen expert ids [expert layers, T,
    k]."""
    import jax
    import jax.numpy as jnp
    from jax import lax

    f32 = jnp.float32
    H, Dh, Dv = c["num_attention_heads"], c["head_dim"], c["v_head_dim"]
    rd = c.get("_rotary_dim", int(c["partial_rotary_factor"] * Dh))
    thetas = (float(c["rope_theta"]), float(c["swa_rope_theta"]))
    if c.get("_thetas_swapped"):
        thetas = thetas[::-1]
    v_scale = 1.0 if c.get("_no_v_scale") else c["attention_value_scale"]
    window = c.get("_window", c["sliding_window"])
    eps = float(c["layernorm_epsilon"])
    T = tokens.shape[0]
    qb = min(query_block, T)
    n_qb = -(-T // qb)
    Tp = n_qb * qb
    positions = jnp.arange(T)

    def lo(a):
        a = a.astype(f32)
        if round_to is None:
            return a
        # a saturating cast: an 8-bit float has no infinity
        top = float(jnp.finfo(round_to).max)
        return jnp.clip(a, -top, top).astype(round_to).astype(f32)

    def rms(x, w):
        return x * lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) \
            * w.astype(f32)

    def rope(x, theta):                            # [T, heads, Dh]
        half = rd // 2
        freqs = theta ** (-jnp.arange(half, dtype=f32) / half)
        ang = positions.astype(f32)[:, None, None] * freqs[None, None, :]
        cos, sin = jnp.cos(ang), jnp.sin(ang)
        a, b, rest = x[..., :half], x[..., half:rd], x[..., rd:]
        return jnp.concatenate([a * cos - b * sin, a * sin + b * cos, rest],
                               -1)

    def column_blocks(width):
        n = width_blocks if width % width_blocks == 0 else 1
        return n, width // n

    def attention(x, lp, windowed):
        u = rms(x, lp["ln1"])
        q = jnp.einsum("td,dhk->thk", lo(u), lo(lp["wq"]))
        k = jnp.einsum("td,dgk->tgk", lo(u), lo(lp["wk"]))
        v = v_scale * jnp.einsum("td,dgk->tgk", lo(u), lo(lp["wv"]))
        G = k.shape[1]
        if G != (c["swa_num_key_value_heads"] if windowed
                 else c["num_key_value_heads"]):
            raise ValueError(f"{G} key-value heads in a "
                             f"{'window' if windowed else 'full'} layer")
        q, k = rope(q, thetas[windowed]), rope(k, thetas[windowed])
        sink = None
        if windowed and not c.get("_no_sink"):
            sink = lp["sink"].astype(f32).reshape(G, H // G)

        def attend(qq, pp):                        # [qb, H, Dh], [qb]
            qg = qq.reshape(qb, G, H // G, Dh)
            s = jnp.einsum("qgrd,sgd->qgrs", qg, k) * Dh ** -0.5
            back = pp[:, None] - positions[None, :]
            seen = back >= 0
            if windowed:
                seen &= back < window
            s = jnp.where(seen[:, None, None, :], s, -jnp.inf)
            if sink is not None:
                # the sink: one more column in the softmax, then dropped
                s = jnp.concatenate(
                    [s, jnp.broadcast_to(sink[None, :, :, None],
                                         s.shape[:-1] + (1,))], -1)
            p = jax.nn.softmax(s, -1)[..., :T]
            return jnp.einsum("qgrs,sgd->qgrd", p, v)

        cut = [jnp.pad(a, [(0, Tp - T)] + [(0, 0)] * (a.ndim - 1)
                       ).reshape((n_qb, qb) + a.shape[1:])
               for a in (q, positions)]
        o = lax.map(lambda args: attend(*args), tuple(cut))
        o = o.reshape(Tp, H, Dv)[:T]
        return x + jnp.einsum("thk,hkd->td", lo(o), lo(lp["wo"]))

    def dense_ffn(h, lp):
        n, w = column_blocks(lp["w_gate"].shape[1])

        def block(i, acc):
            gate = lax.dynamic_slice_in_dim(lp["w_gate"], i * w, w, 1)
            up = lax.dynamic_slice_in_dim(lp["w_up"], i * w, w, 1)
            down = lax.dynamic_slice_in_dim(lp["w_down"], i * w, w, 0)
            mid = jax.nn.silu(lo(h) @ lo(gate)) * (lo(h) @ lo(up))
            return acc + lo(mid) @ lo(down)
        return lax.fori_loop(0, n, block, jnp.zeros_like(h))

    def head(x):
        wlm = params["wlm"]
        n, w = column_blocks(wlm.shape[1])

        def block(i, out):
            cols = lo(lax.dynamic_slice_in_dim(wlm, i * w, w, 1))
            return lax.dynamic_update_slice_in_dim(out, lo(x) @ cols,
                                                   i * w, 1)
        return lax.fori_loop(0, n, block,
                             jnp.zeros((T, wlm.shape[1]), f32))

    routes = []
    with jax.default_matmul_precision("highest"):
        x = jnp.take(params["wte"], tokens, axis=0).astype(f32)
        for l, lp in enumerate(params["layers"]):
            x = attention(x, lp, int(c["hybrid_layer_pattern"][l]))
            h = rms(x, lp["ln2"])
            if c["moe_layer_freq"][l]:
                out, ids = moe(h, lp, c, lo, with_routes=True)
                routes.append(ids)
            else:
                out = dense_ffn(h, lp)
            x = x + out
        logits = head(rms(x, params["ln_f"]))
    return (logits, jnp.stack(routes)) if with_routes else logits
