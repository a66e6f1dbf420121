"""The plain reference of K-EXAONE (`exaone_moe`), from the equations:
float32 `jax.numpy`, matmul precision `highest`, no cache, no pages, no
rings, no grouped matmul, no batching.  It shares no code with
`ray_tpu.models` and imports jax alone.

It takes the SAME weights the program serves (bf16 values, the program's
layout: a tuple of layers) and upcasts a matrix, or one expert, at a
time: attention is computed in blocks of queries against ALL keys under
a full mask, the dense feed-forward and the output head in blocks of
columns, the experts one by one, so ~2,000 positions fit beside a
serving replica's weights and cache.

The equations (`c` is the configuration file's dict; x is the residual
stream [T, hidden_size]; RMSNorm eps `rms_norm_eps`):

  block (pre-norm):  h = x + Attn_l(rms(x)),  y = h + FF_l(rms(h)).

  attention: q = W_q h (num_attention_heads x head_dim), k = W_k h,
  v = W_v h (num_key_value_heads x head_dim), no biases; q and k each
  pass an RMSNorm over the head_dim of a head; in a layer whose
  `sliding_windows[l]` is W > 0 (a window layer) q and k are rotated
  (RoPE, theta `rope_parameters.rope_theta`, pairs (i, i + head_dim/2))
  and key s is visible to query t iff 0 <= t - s < W; in a layer whose
  entry is 0 (a global layer) nothing is rotated and every key s <= t is
  visible.  Scores q . k / sqrt(head_dim); query head j reads key-value
  head j // (heads / kv heads); o = W_o concat(heads).

  feed-forward: SwiGLU of `intermediate_size` in the first
  `first_k_dense_replace` layers.  After them: s = sigmoid(h W_r) over
  ALL published experts in float32; the `num_experts_per_tok` largest of
  s + b are chosen (b: the router's selection bias); a chosen expert's
  weight is `routed_scaling_factor` x s_i / (sum of s over the chosen +
  1e-20).  The layer adds shared(h), one SwiGLU of `num_shared_experts`
  x `moe_intermediate_size`, and the sum over chosen experts of
  weight x SwiGLU_e(h) — OVER THE EXPERTS HELD HERE ONLY: experts
  `expert_offset` .. `expert_offset + num_experts - 1` of the published
  count.  What the others would add is left out, as in the program.

  head: rms, then W_head (untied).  The multi-token-prediction layer is
  not part of these logits and is not here.

`c` may carry switches that only tools/kexaone_limits.py and the tests
write (`_window_ignored`, `_window`, `_rope_global`, `_no_qk_norm`,
`_top_k`, `_no_renorm`, `_routed_scale`, `_no_shared`): the controls a
comparison must catch.
"""

from __future__ import annotations


def chosen_experts(s, bias, c):
    """s [N, E] float32 sigmoid scores -> (ids [N, k], weights [N, k])."""
    import jax.numpy as jnp
    from jax import lax

    k = c.get("_top_k", c["num_experts_per_tok"])
    ids = lax.top_k(s + bias[None, :], k)[1]
    w = jnp.take_along_axis(s, ids, axis=1)
    if not c.get("_no_renorm"):
        w = w / (w.sum(-1, keepdims=True) + 1e-20)
    return ids, w * c.get("_routed_scale", c["routed_scaling_factor"])


def moe(h, lp, c, lo=lambda a: a, with_routes=False):
    """The expert layer on normed h [T, D] float32: shared(h) + the held
    experts' part, each held expert applied to every token under a mask.
    `lp` the layer's weights; the held experts are the published ones
    `expert_offset` .. + `num_experts`."""
    import jax
    import jax.numpy as jnp
    from jax import lax

    f32 = jnp.float32
    E_all = c["published"]["num_experts"]
    held, first = c["num_experts"], c.get("expert_offset", 0)
    s = jax.nn.sigmoid(h @ lp["router"].astype(f32))
    if s.shape[1] != E_all:
        raise ValueError(f"the router scores {s.shape[1]} experts, the "
                         f"published count is {E_all}")
    ids, w = chosen_experts(s, lp["router_bias"].astype(f32), c)
    # weight of published expert e for each token (0 where not chosen)
    dense_w = jnp.zeros((h.shape[0], E_all), f32).at[
        jnp.arange(h.shape[0])[:, None], ids].add(w)

    def swiglu(x, gate, up, down):
        mid = jax.nn.silu(lo(x) @ lo(gate.astype(f32))) \
            * (lo(x) @ lo(up.astype(f32)))
        return lo(mid) @ lo(down.astype(f32))

    ex = lp["experts"]

    def one(e, acc):
        y = swiglu(h, ex["w_gate"][e], ex["w_up"][e], ex["w_down"][e])
        return acc + lax.dynamic_slice_in_dim(dense_w, first + e, 1, 1) * y
    out = lax.fori_loop(0, held, one, jnp.zeros_like(h))
    if not c.get("_no_shared"):
        sh = lp["shared"]
        out = out + swiglu(h, sh["w_gate"], sh["w_up"], sh["w_down"])
    return (out, ids) if with_routes else out


def forward(params, tokens, c, query_block=128, width_blocks=8,
            round_to=None, with_routes=False):
    """tokens [T] int32 -> logits [T, V] float32.  `round_to` (a dtype
    name, e.g. "float8_e4m3fn") rounds both inputs of every weight
    matmul to that type first: the reference in a lower precision, for
    setting the comparison's limits (tools/kexaone_limits.py), never for
    a judged run.  `with_routes` also returns the chosen expert ids
    [expert layers, T, k]."""
    import jax
    import jax.numpy as jnp
    from jax import lax

    f32 = jnp.float32
    H, G, Dh = (c["num_attention_heads"], c["num_key_value_heads"],
                c["head_dim"])
    theta = float(c["rope_parameters"]["rope_theta"])
    eps = float(c["rms_norm_eps"])
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

    def rope(x):                                   # [T, heads, Dh]
        half = Dh // 2
        freqs = theta ** (-jnp.arange(half, dtype=f32) / half)
        ang = positions.astype(f32)[:, None, None] * freqs[None, None, :]
        cos, sin = jnp.cos(ang), jnp.sin(ang)
        a, b = x[..., :half], x[..., half:]
        return jnp.concatenate([a * cos - b * sin, a * sin + b * cos], -1)

    def column_blocks(width):
        n = width_blocks if width % width_blocks == 0 else 1
        return n, width // n

    def attention(x, lp, window):
        h = rms(x, lp["ln1"])
        q = jnp.einsum("td,dhk->thk", lo(h), lo(lp["wq"]))
        kv = jnp.einsum("td,dchk->tchk", lo(h), lo(lp["wkv"]))
        k, v = kv[:, 0], kv[:, 1]
        if not c.get("_no_qk_norm"):
            q, k = rms(q, lp["qn"]), rms(k, lp["kn"])
        if window or c.get("_rope_global"):
            q, k = rope(q), rope(k)
        if window and c.get("_window_ignored"):
            window = 0
        elif window:
            window = c.get("_window", window)

        def attend(qq, pp):                        # [qb, H, Dh], [qb]
            qg = qq.reshape(qb, G, H // G, Dh)
            s = jnp.einsum("qgrd,sgd->qgrs", qg, k) * Dh ** -0.5
            back = pp[:, None] - positions[None, :]
            seen = back >= 0
            if window:
                seen &= back < window
            s = jnp.where(seen[:, None, None, :], s, -jnp.inf)
            return jnp.einsum("qgrs,sgd->qgrd", jax.nn.softmax(s, -1), v)

        cut = [jnp.pad(a, [(0, Tp - T)] + [(0, 0)] * (a.ndim - 1)
                       ).reshape((n_qb, qb) + a.shape[1:])
               for a in (q, positions)]
        o = lax.map(lambda args: attend(*args), tuple(cut))
        o = o.reshape(Tp, H, Dh)[:T]
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
            x = attention(x, lp, c["sliding_windows"][l])
            h = rms(x, lp["ln2"])
            if "router" in lp:
                out, ids = moe(h, lp, c, lo, with_routes=True)
                routes.append(ids)
            else:
                out = dense_ffn(h, lp)
            x = x + out
        logits = head(rms(x, params["ln_f"]))
    return (logits, jnp.stack(routes)) if with_routes else logits
