"""The plain reference of Jamba (`model_type` `jamba`), from the
equations: float32 `jax.numpy`, matmul precision `highest`, the
recurrence written token by token, an explicit sum over the
convolution's taps, a full [T, T] causal mask, no cache, no pages, no
kernels, no batching.  It shares no code with `ray_tpu` and imports jax
alone.

It takes the SAME weights the program serves (bf16 values in the
program's layout: one stack per run of equal layers) and upcasts a
layer's at a time.

The equations (`c` is the configuration file's dict; x is the residual
stream [T, hidden_size]; RMSNorm eps `rms_norm_eps`; E = `mamba_expand`
x hidden_size, N = `mamba_d_state`, K = `mamba_d_conv`, R =
`mamba_dt_rank`):

  layer i is attention iff i mod `attn_layer_period` ==
  `attn_layer_offset`, else Mamba.

  block (pre-norm):  h = x + Mixer_i(rms(x)),  y = h + FF(rms(h)),
  FF(u) = W_down(silu(W_gate u) * W_up u), no biases.

  Mamba mixer, u = rms(x):  [a, z] = W_in u (2 x E, no bias).
  c_t = silu(b_conv + sum_{j<K} w_conv[j] * a_{t-(K-1)+j}) per channel
  (a before the sequence is zero).  [d, B, C] = W_x c (R + N + N, no
  bias), each then passes an RMSNorm of its own width.
  delta = softplus(W_dt d + b_dt) [T, E];  A = -exp(A_log) [N, E].
  h_t = exp(delta_t * A) * h_{t-1} + (delta_t * c_t) * B_t[:, None],
  h_{-1} = 0 (state [N, E]: the program's layout, channels minor);
  y_t = sum_n h_t[n] * C_t[n] + D * c_t;  out = W_out (y * silu(z)).

  attention, u = rms(x):  q = W_q u (`num_attention_heads` x 128),
  k = W_k u, v = W_v u (ONE head of 128), no biases, NO positional
  encoding; scores q . k / sqrt(128), causal, every query head reads
  the one key-value head; o = W_o concat(heads).

  head: rms, then the embedding's transpose (tied).

`c` may carry switches that only tools/jamba_limits.py and the tests
write: the controls a comparison must catch.  `_state_reset_every` /
`_tail_reset_every` (a chunk length: the state / the convolution's tail
is dropped at every multiple of it, as a program that did not carry it
across a chunk boundary would), `_state_dtype` (the state is rounded to
that type after every token), `_no_dtbc_norms`, `_no_D`, `_no_softplus`
(delta = the bare projection's absolute value, so the decay stays a
decay), `_linear_decay` (1 + delta A for exp(delta A)), `_rope` (RoPE
of that theta applied to q and k in the attention layers), `_no_conv_bias`.
"""

from __future__ import annotations


def layer_kinds(c):
    return ["attention" if i % c["attn_layer_period"]
            == c["attn_layer_offset"] else "mamba"
            for i in range(c["num_hidden_layers"])]


def forward(params, tokens, c, round_to=None):
    """tokens [T] int32 -> logits [T, V] float32.  `round_to` (a dtype
    name, e.g. "float8_e4m3fn") rounds both inputs of every weight
    matmul to that type first: the reference in a lower precision, for
    setting the comparison's limits (tools/jamba_limits.py), never for a
    judged run."""
    import jax
    import jax.numpy as jnp
    from jax import lax

    f32 = jnp.float32
    Dh = c["hidden_size"] // c["num_attention_heads"]
    N, K, R = c["mamba_d_state"], c["mamba_d_conv"], c["mamba_dt_rank"]
    eps = float(c["rms_norm_eps"])
    T = tokens.shape[0]
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
        half = Dh // 2
        freqs = theta ** (-jnp.arange(half, dtype=f32) / half)
        ang = positions.astype(f32)[:, None, None] * freqs[None, None, :]
        cos, sin = jnp.cos(ang), jnp.sin(ang)
        a, b = x[..., :half], x[..., half:]
        return jnp.concatenate([a * cos - b * sin, a * sin + b * cos], -1)

    def dropped_at(every):
        """[T] bool: positions at which a chunk of `every` begins."""
        if not every:
            return jnp.zeros((T,), bool)
        return (positions % every == 0) & (positions > 0)

    def mamba(x, lp):
        u = rms(x, lp["ln1"])
        az = jnp.einsum("td,dce->tce", lo(u), lo(lp["w_in"]))
        a, z = az[:, 0], az[:, 1]
        # the convolution: tap j reads the input K - 1 - j tokens back
        w = lp["w_conv"].astype(f32)
        acc = jnp.zeros_like(a)
        if not c.get("_no_conv_bias"):
            acc = acc + lp["b_conv"].astype(f32)[None]
        for j in range(K):
            back = K - 1 - j
            shifted = jnp.pad(a, ((back, 0), (0, 0)))[:T]
            reach = positions - back
            seen = reach >= 0
            if c.get("_tail_reset_every"):   # nothing before its chunk
                seen &= reach >= positions - positions \
                    % c["_tail_reset_every"]
            acc = acc + w[j][None] * jnp.where(seen[:, None], shifted, 0.0)
        cc = jax.nn.silu(acc)
        dbc = lo(cc) @ lo(lp["w_x"])
        d, Bm, Cm = dbc[:, :R], dbc[:, R:R + N], dbc[:, R + N:]
        if not c.get("_no_dtbc_norms"):
            d, Bm, Cm = (rms(d, lp["dt_ln"]), rms(Bm, lp["b_ln"]),
                         rms(Cm, lp["c_ln"]))
        pre = lo(d) @ lo(lp["w_dt"]) + lp["b_dt"].astype(f32)[None]
        delta = jnp.abs(pre) if c.get("_no_softplus") \
            else jax.nn.softplus(pre)
        A = -jnp.exp(lp["a_log"].astype(f32))                # [N, E]
        state_dt = c.get("_state_dtype")
        drop = dropped_at(c.get("_state_reset_every"))

        def token(h, inp):
            dl, ct, bt, ct_out, fresh = inp
            h = jnp.where(fresh, 0.0, h)
            decay = 1.0 + dl[None, :] * A if c.get("_linear_decay") \
                else jnp.exp(dl[None, :] * A)
            h = decay * h + (dl * ct)[None, :] * bt[:, None]
            if state_dt:     # (a cast there and back is optimised away)
                h = lax.reduce_precision(h, jnp.finfo(state_dt).nexp,
                                         jnp.finfo(state_dt).nmant)
            return h, (h * ct_out[:, None]).sum(0)

        _, y = lax.scan(token, jnp.zeros(A.shape, f32),
                        (delta, cc, Bm, Cm, drop))
        if not c.get("_no_D"):
            y = y + lp["d_skip"].astype(f32)[None] * cc
        return x + lo(y * jax.nn.silu(z)) @ lo(lp["w_out"])

    def attention(x, lp):
        u = rms(x, lp["ln1"])
        q = jnp.einsum("td,dhk->thk", lo(u), lo(lp["wq"]))
        kv = jnp.einsum("td,dck->tck", lo(u), lo(lp["wkv"]))
        k, v = kv[:, 0], kv[:, 1]
        if c.get("_rope"):
            q = rope(q, float(c["_rope"]))
            k = rope(k[:, None], float(c["_rope"]))[:, 0]
        s = jnp.einsum("thd,sd->hts", q, k) * Dh ** -0.5
        seen = positions[None, :] <= positions[:, None]      # [T, T]
        s = jnp.where(seen[None], s, -jnp.inf)
        o = jnp.einsum("hts,sd->thd", jax.nn.softmax(s, -1), v)
        return x + jnp.einsum("thk,hkd->td", lo(o), lo(lp["wo"]))

    def ffn(x, lp):
        h = rms(x, lp["ln2"])
        mid = jax.nn.silu(lo(h) @ lo(lp["w_gate"])) \
            * (lo(h) @ lo(lp["w_up"]))
        return x + lo(mid) @ lo(lp["w_down"])

    kinds = layer_kinds(c)
    with jax.default_matmul_precision("highest"):
        x = jnp.take(params["wte"], tokens, axis=0).astype(f32)
        l = 0
        for stack in params["runs"]:
            n = stack["ln1"].shape[0]
            kind = kinds[l]
            if kinds[l:l + n] != [kind] * n:
                raise ValueError("a stack of weights spans layers of two "
                                 "kinds")

            def layer(x, lp, kind=kind):
                x = attention(x, lp) if kind == "attention" \
                    else mamba(x, lp)
                return ffn(x, lp), None
            x, _ = lax.scan(layer, x, stack)
            l += n
        if l != len(kinds):
            raise ValueError(f"{l} layers of weights, {len(kinds)} in the "
                             f"configuration")
        x = rms(x, params["ln_f"])
        return lo(x) @ lo(params["wte"]).T
