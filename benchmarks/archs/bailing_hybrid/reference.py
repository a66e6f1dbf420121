"""The plain reference of Ling-3.0-flash (`bailing_hybrid`), from the
equations: float32 `jax.numpy`, matmul precision `highest`, no cache, no
pages, no chunks, no grouped matmul.  It shares no code with
`ray_tpu.models` or `ray_tpu.ops` and imports jax alone.

It takes the SAME weights the program serves (bf16 values, the program's
layout: a tuple of layers) and upcasts a matrix, or one expert, at a
time.  `c` is the configuration file's dict.  A layer is pre-norm
(RMSNorm, eps `rms_norm_eps`): x = x + mixer(rms(x)); x = x +
ffn(rms(x)); after the last layer rms, then the untied head.

  layer kinds: the layer whose PUBLISHED index l (`layer_offset` + its
  index here) has (l + 1) % layer_group_size == 0 is MLA; every other is
  KDA.

  KDA mixer (h the normed input, H heads of d = 128): q~, k~, v~ = h Wq,
  h Wk, h Wv (one array `wqkv`, [D, 3 x 4096]); each stream through a
  causal depthwise convolution of 4 taps over time AS AN EXPLICIT 4-TERM
  SUM, zeros before position 0, no bias, then SiLU; per head q =
  l2norm(q) / sqrt(d), k = l2norm(k) (x / sqrt(sum x^2 + 1e-6)); the
  log-decay per key channel a_t = kda_lower_bound x sigmoid(exp(A_log_h)
  x (h Wf + dt_bias)), alpha_t = exp(a_t); beta_t = sigmoid(h Wb); the
  state S [128 (key), 128 (value)] a head, zero at position 0, TOKEN BY
  TOKEN (a `lax.scan` over T; no chunks, no cumulative decays):

      S'  = diag(alpha_t) S_{t-1}
      S_t = S' + beta_t k_t (v_t - S'^T k_t)^T
      o_t = S_t^T q_t

  y = [rms_head(o_t; w_o) * sigmoid(h Wg)] Wo, the norm over each head's
  128, the gate element-wise.  No positional encoding.

  MLA mixer, EXPANDED at every position under a full causal mask (in
  blocks of queries): q = h Wq [T, H, 128 + 64] (one projection);
  [c ; kr] = h Wkva, c (512) normed, kr (64) shared by all heads;
  k_nope = c Wk_b, v = c Wv_b; RoPE on q's 64 and on kr, theta
  `rope_theta`, INTERLEAVED pairs (2i, 2i + 1), no scaling; scores
  (q_nope . k_nope + q_rope . kr) / sqrt(192); softmax; o_h = sum p v;
  o_h = o_h x sigmoid(h wg_h) (one scalar a head); y = concat(o_h) Wo.

  expert layer: s = sigmoid(h2 Wr) over ALL published experts, float32;
  for the choice only s' = s + bias; a group (of n_group) scores the sum
  of its two largest s'; the topk_group best groups are kept; the
  num_experts_per_tok largest s' inside them are chosen; weights the
  UNBIASED s renormalised over the chosen, times routed_scaling_factor.
  The layer adds shared(h2), one SwiGLU of moe_intermediate_size, and
  the sum over chosen experts of weight x SwiGLU_e(h2) - OVER THE
  EXPERTS HELD HERE ONLY (`expert_offset` .. + `num_experts` - 1 of the
  published count), each held expert applied to every token under a
  mask.  The first `first_k_dense_replace` layers here: one SwiGLU of
  `intermediate_size`.

`c` may carry switches that only tools/ling3_limits.py and the tests
write (SWITCHES): the controls a comparison must catch.
"""

from __future__ import annotations

import math

# what a control changes, by the key it sets in `c`
SWITCHES = (
    "_no_decay",          # alpha = 1
    "_no_delta",          # S = diag(alpha) S + beta k v^T
    "_beta_one",          # beta = 1
    "_state_reset_every",  # the state not carried past every so many tokens
    "_tail_reset_every",  # the convolution's tail not carried past them
    "_no_l2norm",         # q, k as the convolution leaves them (q / sqrt d)
    "_no_out_gate",       # KDA's output gate left out
    "_state_dtype",       # the state rounded to this type after every token
    "_no_router_bias", "_no_group_limit", "_top_k", "_routed_scale",
    "_no_head_gate",      # MLA's gate a head left out
    "_no_rope")


def chosen_experts(s, bias, c):
    """s [N, E] float32 sigmoid scores -> (ids [N, k], weights [N, k])."""
    import jax.numpy as jnp
    from jax import lax

    N, E = s.shape
    G = c["n_group"]
    k = c.get("_top_k", c["num_experts_per_tok"])
    by = s if c.get("_no_router_bias") else s + bias[None]
    if not c.get("_no_group_limit"):
        best = lax.top_k(by.reshape(N, G, E // G), 2)[0].sum(-1)
        kept = lax.top_k(best, c["topk_group"])[1]
        in_kept = jnp.zeros((N, G), bool).at[
            jnp.arange(N)[:, None], kept].set(True)
        by = jnp.where(jnp.repeat(in_kept, E // G, axis=1), by, -jnp.inf)
    ids = lax.top_k(by, k)[1]
    w = jnp.take_along_axis(s, ids, axis=1)
    return ids, w / w.sum(-1, keepdims=True) \
        * c.get("_routed_scale", c["routed_scaling_factor"])


def moe(h, lp, c, lo=lambda a: a, with_routes=False, with_shared=True):
    """The expert layer on normed h [T, D] float32: shared(h) + the held
    experts' part.  `with_shared` False leaves the shared expert out (a
    test adds the routed parts of all 8 shares and the shared one once)."""
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
    if with_shared:
        sh = lp["shared"]
        out = out + swiglu(h, sh["w_gate"], sh["w_up"], sh["w_down"])
    return (out, ids) if with_routes else out


def forward(params, tokens, c, query_block=512, round_to=None,
            with_routes=False):
    """tokens [T] int32 -> logits [T, V] float32.  `round_to` (a dtype
    name) rounds both inputs of every weight matmul to that type first:
    the reference in a lower precision, for setting the comparison's
    limits (tools/ling3_limits.py), never for a judged run.
    `with_routes` also returns the chosen expert ids [expert layers, T,
    k] and the mean decay over (token, KDA layer, channel)."""
    import jax
    import jax.numpy as jnp
    from jax import lax

    f32 = jnp.float32
    eps = float(c["rms_norm_eps"])
    H, d = c["num_attention_heads"], c["head_dim"]
    E = H * d
    K = c["short_conv_kernel_size"]
    dn, dr, dv = (c["qk_nope_head_dim"], c["qk_rope_head_dim"],
                  c["v_head_dim"])
    kr = c["kv_lora_rank"]
    T = tokens.shape[0]
    positions = jnp.arange(T)
    if round_to is not None:
        round_to = jnp.dtype(round_to)

    def lo(a):
        a = a.astype(f32)
        if round_to is None:
            return a
        top = float(jnp.finfo(round_to).max)   # an 8-bit float has no inf
        return jnp.clip(a, -top, top).astype(round_to).astype(f32)

    def rms(x, w):
        return x * lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) \
            * w.astype(f32)

    def swiglu(h, lp):
        mid = jax.nn.silu(lo(h) @ lo(lp["w_gate"])) * (lo(h) @ lo(lp["w_up"]))
        return lo(mid) @ lo(lp["w_down"])

    decays = []

    def kda(x, lp):
        h = rms(x, lp["ln1"])
        u = lo(h) @ lo(lp["wqkv"])                          # [T, 3E]
        w = lp["conv"].astype(f32)                          # [K, 3E]
        every = c.get("_tail_reset_every")
        conv = jnp.zeros_like(u)
        for j in range(K):                  # tap j multiplies u_{t-(K-1)+j}
            back = K - 1 - j
            moved = jnp.pad(u, ((back, 0), (0, 0)))[:T]
            if every:   # nothing from before the token's own chunk
                moved = jnp.where(
                    ((positions - back) >= positions // every * every)
                    [:, None], moved, 0.0)
            conv = conv + w[j][None] * moved
        conv = jax.nn.silu(conv)
        heads = lambda a: a.reshape(T, H, d)                # noqa: E731
        q, k, v = (heads(conv[:, j * E:(j + 1) * E]) for j in range(3))
        if not c.get("_no_l2norm"):
            q = q * lax.rsqrt((q * q).sum(-1, keepdims=True) + eps)
            k = k * lax.rsqrt((k * k).sum(-1, keepdims=True) + eps)
        q = q / math.sqrt(d)
        g = heads(lo(h) @ lo(lp["wf"]))
        a = float(c["kda_lower_bound"]) * jax.nn.sigmoid(
            jnp.exp(lp["a_log"].astype(f32))[None, :, None]
            * (g + lp["dt_bias"].astype(f32).reshape(1, H, d)))
        alpha = jnp.ones_like(a) if c.get("_no_decay") else jnp.exp(a)
        decays.append(alpha.mean())
        beta = jax.nn.sigmoid(lo(h) @ lo(lp["wb"]))          # [T, H]
        if c.get("_beta_one"):
            beta = jnp.ones_like(beta)
        reset = c.get("_state_reset_every")
        state_dtype = c.get("_state_dtype")

        def token(S, inp):
            q, k, v, alpha, beta, t = inp
            if reset:
                S = jnp.where(t % reset == 0, 0.0, S)
            Sd = alpha[..., None] * S                       # [H, dk, dv]
            old = 0.0 if c.get("_no_delta") \
                else jnp.einsum("hkv,hk->hv", Sd, k)
            S = Sd + k[..., None] * (beta[:, None] * (v - old))[:, None, :]
            if state_dtype:   # (a pair of converts is elided on a TPU)
                bits = jnp.finfo(state_dtype)
                S = lax.reduce_precision(S, bits.nexp, bits.nmant)
            return S, jnp.einsum("hkv,hk->hv", S, q)

        _, o = lax.scan(token, jnp.zeros((H, d, d), f32),
                        (q, k, v, alpha, beta, positions))
        y = rms(o, lp["o_norm"]).reshape(T, E)
        if not c.get("_no_out_gate"):
            y = y * jax.nn.sigmoid(lo(h) @ lo(lp["wg"]))
        return x + lo(y) @ lo(lp["wo"])

    inv_freq = float(c["rope_theta"]) ** (
        -jnp.arange(dr // 2, dtype=f32) / (dr // 2))

    def rope(x):                                            # [T, ..., dr]
        if c.get("_no_rope"):
            return x
        ang = positions.astype(f32)[:, None] * inv_freq[None, :]
        ang = ang.reshape((T,) + (1,) * (x.ndim - 2) + (dr // 2,))
        cos, sin = jnp.cos(ang), jnp.sin(ang)
        a, b = x[..., 0::2], x[..., 1::2]
        return jnp.stack([a * cos - b * sin, a * sin + b * cos],
                         axis=-1).reshape(x.shape)

    qb = min(query_block, T)
    n_qb = -(-T // qb)
    Tp = n_qb * qb

    def mla(x, lp):
        h = rms(x, lp["ln1"])
        q = jnp.einsum("td,dhk->thk", lo(h), lo(lp["wq"]))
        kva = lo(h) @ lo(lp["wkv_a"])
        ckv = rms(kva[:, :kr], lp["kv_norm"])
        k_pe = rope(kva[:, kr:])                            # [T, dr]
        q_nope, q_pe = q[..., :dn], rope(q[..., dn:])
        k_nope = jnp.einsum("sc,hnc->shn", lo(ckv), lo(lp["wk_b"]))
        v = jnp.einsum("sc,hcv->shv", lo(ckv), lo(lp["wv_b"]))

        def attend(qn, qp, pp):               # [qb, H, dn], [qb, H, dr]
            s = (jnp.einsum("qhn,shn->qhs", qn, k_nope)
                 + jnp.einsum("qhr,sr->qhs", qp, k_pe)) / math.sqrt(dn + dr)
            seen = positions[None, :] <= pp[:, None]
            s = jnp.where(seen[:, None, :], s, -jnp.inf)
            return jnp.einsum("qhs,shv->qhv", jax.nn.softmax(s, -1), v)

        cut = [jnp.pad(a, [(0, Tp - T)] + [(0, 0)] * (a.ndim - 1)
                       ).reshape((n_qb, qb) + a.shape[1:])
               for a in (q_nope, q_pe, positions)]
        o = lax.map(lambda args: attend(*args), tuple(cut))
        o = o.reshape(Tp, H, dv)[:T]
        if not c.get("_no_head_gate"):
            o = o * jax.nn.sigmoid(lo(h) @ lo(lp["wg"]))[:, :, None]
        return x + jnp.einsum("thv,hvd->td", lo(o), lo(lp["wo"]))

    routes = []
    first, group = c.get("layer_offset", 0), c["layer_group_size"]
    with jax.default_matmul_precision("highest"):
        x = jnp.take(params["wte"], tokens, axis=0).astype(f32)
        for i, lp in enumerate(params["layers"]):
            x = (mla if (first + i + 1) % group == 0 else kda)(x, lp)
            h = rms(x, lp["ln2"])
            if i < c["first_k_dense_replace"]:
                out = swiglu(h, lp)
            else:
                out, ids = moe(h, lp, c, lo, with_routes=True)
                routes.append(ids)
            x = x + out
        logits = lo(rms(x, params["ln_f"])) @ lo(params["wlm"])
    if with_routes:
        return logits, jnp.stack(routes), jnp.stack(decays).mean()
    return logits
