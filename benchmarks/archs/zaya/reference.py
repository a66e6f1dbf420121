"""The plain reference of ZAYA1 (`zaya`), from the equations: float32
`jax.numpy`, matmul precision `highest`, no cache, no pages, no tails, no
grouped matmul, no batching.  It shares no code with `ray_tpu.models` and
imports jax alone.

It takes the SAME weights the program serves (bf16 values, the program's
layout: a tuple of layers) and upcasts a matrix, or one expert, at a
time; the tied head is computed in blocks of vocabulary rows, so ~1,000
positions fit beside a serving replica's weights and cache.

The equations (`c` is the configuration file's dict; x is the residual
stream [T, hidden_size]; `rms` an RMSNorm with a learned gain, eps
`rms_norm_eps`; d = `head_dim`; a quantity at t - 1 is ZERO at t = 0;
convolutions are explicit shifts with zero padding):

  layer l:  h = J_a(x, CCA(rms(x))),  y = J_m(h, MoE(rms(h), r_{l-1}));
  J(x, f) = (a * x + c) + (a' * f + c'), four learned vectors a
  sublayer.  After the last layer: logits = rms(y) E^T, E the tied
  embedding.

  CCA, u = rms(x).  z_t = [W_q u_t ; W_k u_t]: `num_attention_heads` + G
  heads of d, the query heads first (G = `num_key_value_heads`).
    c0_t = w0a * z_{t-1} + w0b * z_t + b0            (`cca_time0` = 2,
                                                      depthwise)
    c1_t[g] = c0_{t-1}[g] W1a[g] + c0_t[g] W1b[g] + b1[g]
                                                     (`cca_time1` = 2,
                                                      within head g)
    m^q_t[h] = (z_t[h] + z_t[key head of h]) / 2;  m^k_t[g] = the mean of
    m^q_t over the query heads of g.  q = c1[query part] + m^q,
    k = c1[key part] + m^k.
    q^ = q / rms(q), k^ = tau_g k / rms(k) per head (no gain; eps
    `rms_norm_eps`); RoPE on the first `partial_rotary_factor` x d
    dimensions, pairs (i, i + half), theta `rope_parameters.hybrid`.
    v_t = [W_v1 u_t ; W_v2 u_{t-1}]: head 0 from this token, head 1 from
    the one before.
    s_tj = q^_t . k^_j / sqrt(d), j <= t, a plain softmax under a full
    [T, T] mask; query head h reads key-value head h // (heads / G);
    CCA = W_o concat(heads).

  MoE, u = rms(h), all float32: r_l = u W_d + b_d + gamma_l * r_{l-1}
  (no carry in layer 0; r_l goes on as it is); g = gelu(gelu(rms(r_l)
  W_1 + b_1) W_2 + b_2) W_3 (gelu in its erf form); p = softmax(g) over
  `num_experts`; the `num_experts_per_tok` largest of p + beta are
  chosen; MoE = sum over the chosen of p_e SwiGLU_e(u): p itself, not
  renormalised.  No shared expert.

`c` may carry switches that only tools/zaya_limits.py and the tests
write (`_no_conv`, `_conv1_depthwise`, `_no_mean`, `_v_now`, `_tau_one`,
`_rotary_dim`, `_no_carry`, `_top_k`, `_gate_one`, `_no_join`): the
controls a comparison must catch.
"""

from __future__ import annotations


def forward(params, tokens, c, width_blocks=8, round_to=None,
            with_routes=False):
    """tokens [T] int32 -> logits [T, V] float32.  `round_to` (a dtype
    name, e.g. "bfloat16") rounds both inputs of every weight matmul
    outside the router (which the program applies in float32) to that
    type first: the reference in a lower precision, for setting the
    comparison's limits (tools/zaya_limits.py), never for a judged run.
    `with_routes` also returns the chosen expert ids and their weights,
    both [layers, T, k]."""
    import jax
    import jax.numpy as jnp
    from jax import lax

    f32 = jnp.float32
    H, G, d = (c["num_attention_heads"], c["num_key_value_heads"],
               c["head_dim"])
    if (c["cca_time0"], c["cca_time1"]) != (2, 2):
        raise ValueError("both convolutions are written for two taps")
    rd = c.get("_rotary_dim", int(c["partial_rotary_factor"] * d))
    theta = float(c["rope_parameters"]["hybrid"]["rope_theta"])
    eps = float(c["rms_norm_eps"])
    top_k = c.get("_top_k", c["num_experts_per_tok"])
    T = tokens.shape[0]
    positions = jnp.arange(T)
    causal = positions[None, :] <= positions[:, None]          # [T, T]

    def lo(a):
        a = a.astype(f32)
        if round_to is None:
            return a
        # a saturating cast: an 8-bit float has no infinity
        top = float(jnp.finfo(round_to).max)
        return jnp.clip(a, -top, top).astype(round_to).astype(f32)

    def unit(x):
        return x * lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps)

    def rms(x, w):
        return unit(x) * w.astype(f32)

    def late(a):
        """a [T, ...] one token late: zeros at t = 0."""
        return jnp.concatenate([jnp.zeros_like(a[:1]), a[:-1]])

    def rope(x):                                   # [T, heads, d]
        half = rd // 2
        freqs = theta ** (-jnp.arange(half, dtype=f32) / half)
        ang = positions.astype(f32)[:, None, None] * freqs[None, None, :]
        cos, sin = jnp.cos(ang), jnp.sin(ang)
        a, b, rest = x[..., :half], x[..., half:rd], x[..., rd:]
        return jnp.concatenate([a * cos - b * sin, a * sin + b * cos, rest],
                               -1)

    def join(x, f, j):
        if c.get("_no_join"):
            return x + f
        j = j.astype(f32)
        return (j[0] * x + j[1]) + (j[2] * f + j[3])

    def cca(x, lp):
        u = rms(x, lp["ln1"])
        z = jnp.einsum("td,dhk->thk", lo(u), lo(lp["wqk"]))
        vv = jnp.einsum("td,dgk->tgk", lo(u), lo(lp["wv"]))
        w0, w1 = lp["w0"].astype(f32), lp["w1"]
        if c.get("_no_conv"):
            c1 = z
        else:
            c0 = w0[0] * late(z) + w0[1] * z + lp["b0"].astype(f32)
            if c.get("_conv1_depthwise"):
                tap = lambda i: jnp.diagonal(  # noqa: E731
                    w1[i].astype(f32), axis1=-2, axis2=-1)
                c1 = tap(0) * late(c0) + tap(1) * c0
            else:
                c1 = jnp.einsum("tgk,gkj->tgj", lo(late(c0)), lo(w1[0])) \
                    + jnp.einsum("tgk,gkj->tgj", lo(c0), lo(w1[1]))
            c1 = c1 + lp["b1"].astype(f32)
        zq = z[:, :H].reshape(T, G, H // G, d)
        mq = (zq + z[:, H:, None]) / 2
        if c.get("_no_mean"):
            mq = jnp.zeros_like(mq)
        q = c1[:, :H] + mq.reshape(T, H, d)
        k = c1[:, H:] + mq.mean(2)
        tau = 1.0 if c.get("_tau_one") else lp["tau"].astype(f32)[:, None]
        q, k = rope(unit(q)), rope(unit(k) * tau)
        v = jnp.stack([vv[:, 0],
                       vv[:, 1] if c.get("_v_now") else late(vv[:, 1])], 1)
        s = jnp.einsum("tgrd,sgd->grts", q.reshape(T, G, H // G, d), k) \
            * d ** -0.5
        p = jax.nn.softmax(jnp.where(causal[None, None], s, -jnp.inf), -1)
        o = jnp.einsum("grts,sgd->tgrd", p, v).reshape(T, H, d)
        return jnp.einsum("thk,hkd->td", lo(o), lo(lp["wo"]))

    def router(u, rp, r_prev):
        rp = {n: w.astype(f32) for n, w in rp.items()}
        r = u @ rp["wd"] + rp["bd"]
        if r_prev is not None and not c.get("_no_carry"):
            r = r + rp["gamma"] * r_prev
        g = jax.nn.gelu(rms(r, rp["ln"]) @ rp["w1"] + rp["b1"],
                        approximate=False)
        g = jax.nn.gelu(g @ rp["w2"] + rp["b2"], approximate=False)
        p = jax.nn.softmax(g @ rp["w3"], -1)
        if p.shape[1] != c["num_experts"]:
            raise ValueError(f"the router scores {p.shape[1]} experts, "
                             f"the configuration has {c['num_experts']}")
        ids = lax.top_k(p + rp["beta"][None, :], top_k)[1]
        return ids, jnp.take_along_axis(p, ids, axis=1), r

    def moe(u, lp, ids, w):
        """Every expert applied to every token under a mask."""
        if c.get("_gate_one"):
            w = jnp.ones_like(w)
        dense_w = jnp.zeros((T, c["num_experts"]), f32).at[
            positions[:, None], ids].add(w)
        ex = lp["experts"]

        def one(e, acc):
            gate, up, down = (lo(ex[n][e].astype(f32))
                              for n in ("w_gate", "w_up", "w_down"))
            mid = jax.nn.silu(lo(u) @ gate) * (lo(u) @ up)
            return acc + lax.dynamic_slice_in_dim(dense_w, e, 1, 1) \
                * (lo(mid) @ down)
        return lax.fori_loop(0, c["num_experts"], one, jnp.zeros_like(u))

    def head(x):
        wte = params["wte"]
        V = wte.shape[0]
        n = width_blocks if V % width_blocks == 0 else 1
        w = V // n

        def block(i, out):
            rows = lo(lax.dynamic_slice_in_dim(wte, i * w, w, 0))
            return lax.dynamic_update_slice_in_dim(out, lo(x) @ rows.T,
                                                   i * w, 1)
        return lax.fori_loop(0, n, block, jnp.zeros((T, V), f32))

    routes, gates, r = [], [], None
    with jax.default_matmul_precision("highest"):
        x = jnp.take(params["wte"], tokens, axis=0).astype(f32)
        for lp in params["layers"]:
            x = join(x, cca(x, lp), lp["join1"])
            u = rms(x, lp["ln2"])
            ids, w, r = router(u, lp["router"], r)
            routes.append(ids)
            gates.append(w)
            x = join(x, moe(u, lp, ids, w), lp["join2"])
        logits = head(rms(x, params["ln_f"]))
    if with_routes:
        return logits, jnp.stack(routes), jnp.stack(gates)
    return logits
