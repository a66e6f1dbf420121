"""The plain reference of MiniCPM-SALA, from the equations: float32
`jax.numpy`, matmul precision `highest`, no cache, no pages, no chunked
scan.  It shares no code with `ray_tpu.models` and imports jax alone.

It takes the SAME weights the program serves (bf16 values, the program's
layout: one stack per run of equal layers) and upcasts a matrix, or a
block of its columns, at a time: attention is computed in blocks of
queries, the feed-forward in blocks of its width and the output head in
blocks of the vocabulary, so 8,712 positions (2.6 GB of float32 logits)
fit beside a serving replica's 10 GB of weights and its cache.

The equations (`c` is the configuration file's dict):

  x0 = scale_emb * wte[token];  every sub-layer adds
  x + (scale_depth / sqrt(DEPTH)) * f(rms(x)), DEPTH the PUBLISHED
  number of layers (32) also in a depth cut;  logits =
  rms(x) / (hidden_size / dim_model_base) @ wlm.  RMSNorm eps 1e-6.
  FFN: SwiGLU.

  lightning-attn:  q, k, v = x Wq, x Wk, x Wv (32 heads x 128); RMSNorm
  over the head on q and k; RoPE on q and k; per head h with
  lambda_h = exp(-2^(-8(h+1)/32)):  S_t = lambda_h S_{t-1} + k_t^T v_t,
  o_t = q_t S_t / sqrt(128).  Then RMSNorm of o over all heads,
  * sigmoid(x Wg), Wo.  Computed here as the recurrence itself, one
  token at a time.

  minicpm4 (InfLLM-v2):  32 query heads, 2 KV heads, RMSNorm on q and k,
  no RoPE, output * sigmoid(x Wg) before Wo.  A query at position
  t < dense_len: causal softmax attention.  Otherwise: compressed keys
  = means of 32 keys at stride 16; p = softmax over the kernels wholly
  at or before t of q . kbar / sqrt(128); summed over the 16 heads of a
  KV group; a block of 64 scores the maximum over the kernels that
  overlap it; the first `init_blocks` blocks and the `window_size / 64`
  blocks ending at t's own are forced; the `topk` best blocks are kept;
  causal softmax attention over the keys of those blocks only.
"""

from __future__ import annotations

RMS_EPS = 1e-6
ATTN = "minicpm4"


def runs_of(mixer_types):
    """[(kind, count)] of the runs of equal layers, in order."""
    out = []
    for m in mixer_types:
        if out and out[-1][0] == m:
            out[-1][1] += 1
        else:
            out.append([m, 1])
    return [(k, n) for k, n in out]


def chosen_blocks(q, kbar, qpos, sp):
    """Block ids [N, G, topk] for queries q [N, G, R, Dh] at positions
    qpos [N]; kbar [J, G, Dh] the compressed keys in order (J a multiple
    of 4: kernel j covers tokens 16j .. 16j+31, four kernels start in a
    block)."""
    import jax
    import jax.numpy as jnp
    from jax import lax

    block, stride, kernel = (sp["block_size"], sp["kernel_stride"],
                             sp["kernel_size"])
    N, G, R, Dh = q.shape
    J = kbar.shape[0]
    nb = J // 4
    s = jnp.einsum("ngrd,jgd->ngrj", q, kbar) / jnp.sqrt(float(Dh))
    whole = (jnp.arange(J) * stride + kernel - 1)[None, :] <= qpos[:, None]
    s = jnp.where(whole[:, None, None, :], s, -jnp.inf)
    p = jnp.where(whole[:, None, None, :], jax.nn.softmax(s, axis=-1), 0.0)
    g = p.sum(2)                                          # [N, G, J]
    # kernel j overlaps block b when 4b-1 <= j <= 4b+3
    own = g.reshape(N, G, nb, 4).max(-1)
    before = jnp.concatenate([jnp.zeros((N, G, 1)), g[..., 3::4][..., :-1]],
                             axis=-1)
    score = jnp.maximum(own, before)
    b = jnp.arange(nb)[None, :]
    bq = (qpos // block)[:, None]
    forced = (b < sp["init_blocks"]) \
        | ((b <= bq) & (b > bq - sp["window_size"] // block))
    score = jnp.where(forced[:, None, :], jnp.inf, score)
    score = jnp.where((b <= bq)[:, None, :], score, -jnp.inf)
    return lax.top_k(score, sp["topk"])[1]


def forward(params, tokens, c, query_block=128, width_blocks=8,
            round_to=None):
    """tokens [T] int32 -> logits [T, V] float32.  `round_to` (a dtype
    name, e.g. "float8_e4m3fn") rounds both inputs of every weight
    matmul to that type first: the reference in a lower precision, for
    setting the comparison's limits (tools/sala_limits.py), never for a
    judged run."""
    import jax
    import jax.numpy as jnp
    from jax import lax

    f32 = jnp.float32
    sp = c["sparse_config"]
    H, G, Dh = (c["num_attention_heads"], c["num_key_value_heads"],
                c["head_dim"])
    R = H // G
    Hl = c["lightning_nh"]
    theta = float(c["rope_theta"])
    depth = c["published"]["num_hidden_layers"]
    res = c["scale_depth"] / depth ** 0.5
    block, stride = sp["block_size"], sp["kernel_stride"]
    T = tokens.shape[0]
    qb = min(query_block, T)
    n_qb = -(-T // qb)
    Tp = n_qb * qb                                 # queries padded to blocks
    positions = jnp.arange(T)

    def lo(a):
        a = a.astype(f32)
        return a if round_to is None else a.astype(round_to).astype(f32)

    def rms(x, w):
        return x * lax.rsqrt(jnp.mean(x * x, -1, keepdims=True)
                             + RMS_EPS) * w.astype(f32)

    def rope(x):                                   # [T, h, d]
        half = x.shape[-1] // 2
        inv = theta ** (-jnp.arange(half, dtype=f32) / half)
        ang = positions.astype(f32)[:, None] * inv[None, :]
        cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
        a, b = x[..., :half], x[..., half:]
        return jnp.concatenate([a * cos - b * sin, a * sin + b * cos], -1)

    def in_query_blocks(fn, *per_query):
        """fn over blocks of `qb` queries: each argument [T, ...] is
        padded to whole blocks; results [T, ...]."""
        cut = [jnp.pad(a, [(0, Tp - T)] + [(0, 0)] * (a.ndim - 1)
                       ).reshape((n_qb, qb) + a.shape[1:])
               for a in per_query]
        out = lax.map(lambda args: fn(*args), tuple(cut))
        return out.reshape((Tp,) + out.shape[2:])[:T]

    def column_blocks(width):
        n = width_blocks if width % width_blocks == 0 else 1
        return n, width // n

    def ffn(x, lp):
        """SwiGLU, summed over blocks of the feed-forward width (each
        block's three weight slices are upcast on their own)."""
        h = rms(x, lp["ln2"])
        n, w = column_blocks(lp["w_gate"].shape[1])

        def block(i, acc):
            gate = lax.dynamic_slice_in_dim(lp["w_gate"], i * w, w, 1)
            up = lax.dynamic_slice_in_dim(lp["w_up"], i * w, w, 1)
            down = lax.dynamic_slice_in_dim(lp["w_down"], i * w, w, 0)
            mid = jax.nn.silu(lo(h) @ lo(gate)) * (lo(h) @ lo(up))
            return acc + lo(mid) @ lo(down)
        return x + res * lax.fori_loop(0, n, block, jnp.zeros_like(x))

    def proj(h, w):
        return jnp.einsum("td,dhk->thk", lo(h), lo(w))

    def lightning(x, lp):
        h = rms(x, lp["ln1"])
        lam = jnp.exp(-2.0 ** (-8.0 * jnp.arange(1, Hl + 1, dtype=f32) / Hl))
        if c.get("lightning_no_decay"):        # tools/sala_limits.py only
            lam = jnp.ones_like(lam)
        d = lp["wq"].shape[-1]
        n, hw = column_blocks(Hl)

        def heads(i, o):
            """The recurrence of `hw` heads (heads do not mix before
            the output norm), written into their place in o."""
            cut = lambda w: lax.dynamic_slice_in_dim(w, i * hw, hw, 1)  # noqa: E731,E501
            q = rope(rms(proj(h, cut(lp["wq"])), lp["qn"]))
            k = rope(rms(proj(h, cut(lp["wk"])), lp["kn"]))
            v = proj(h, cut(lp["wv"]))
            lam_i = lax.dynamic_slice_in_dim(lam, i * hw, hw)

            def step(S, qkv):
                qt, kt, vt = qkv                   # [hw, d]
                S = lam_i[:, None, None] * S + kt[:, :, None] * vt[:, None, :]
                return S, jnp.einsum("hd,hde->he", qt, S) / jnp.sqrt(float(d))
            _, ob = lax.scan(step, jnp.zeros((hw, d, d), f32), (q, k, v))
            return lax.dynamic_update_slice_in_dim(o, ob, i * hw, 1)
        o = lax.fori_loop(0, n, heads, jnp.zeros((T, Hl, d), f32))
        o = rms(o.reshape(T, Hl * d), lp["on"]).reshape(T, Hl, d)
        gate = jax.nn.sigmoid(proj(h, lp["wg"]))
        return x + res * jnp.einsum("thk,hkd->td", lo(o * gate),
                                    lo(lp["wo"]))

    def sparse_attention(x, lp):
        h = rms(x, lp["ln1"])
        q = rms(proj(h, lp["wq"]), lp["qn"])
        kv = jnp.einsum("td,dchk->tchk", lo(h), lo(lp["wkv"]))
        k, v = rms(kv[:, 0], lp["kn"]), kv[:, 1]   # [T, G, Dh]
        nb = -(-T // block)
        J = nb * 4
        # compressed keys: kernel j = mean of keys 16j .. 16j+31 (zeros
        # where the sequence ends first: such a kernel is never whole)
        kpad = jnp.pad(k, ((0, J * stride + stride - T), (0, 0), (0, 0)))
        halves = kpad.reshape(J + 1, stride, G, Dh).mean(1)
        kbar = 0.5 * (halves[:-1] + halves[1:])    # [J, G, Dh]
        keypos = jnp.arange(T)

        def attend(qq, pp):                        # [qb, H, Dh], [qb]
            qg = qq.reshape(qb, G, R, Dh)
            if T <= sp["dense_len"]:               # no query selects
                picked = jnp.ones((qb, G, nb), bool)
            else:
                blocks = chosen_blocks(qg, kbar, pp, sp)
                picked = jnp.zeros((qb, G, nb), bool).at[
                    jnp.arange(qb)[:, None, None],
                    jnp.arange(G)[None, :, None], blocks].set(True)
                picked = picked | (pp < sp["dense_len"])[:, None, None]
            mask = picked[:, :, keypos // block] \
                & (keypos[None, None, :] <= pp[:, None, None])
            s = jnp.einsum("qgrd,sgd->qgrs", qg, k) / jnp.sqrt(float(Dh))
            s = jnp.where(mask[:, :, None, :], s, -jnp.inf)
            a = jnp.einsum("qgrs,sgd->qgrd", jax.nn.softmax(s, -1), v)
            return a.reshape(qb, H, Dh)

        a = in_query_blocks(attend, q, positions)
        gate = jax.nn.sigmoid(proj(h, lp["wg"]))
        return x + res * jnp.einsum("thk,hkd->td", lo(a * gate),
                                    lo(lp["wo"]))

    def head(x):
        """x @ wlm in blocks of the vocabulary, written in place."""
        wlm = params["wlm"]
        n, w = column_blocks(wlm.shape[1])

        def block(i, out):
            cols = lo(lax.dynamic_slice_in_dim(wlm, i * w, w, 1))
            return lax.dynamic_update_slice_in_dim(out, lo(x) @ cols,
                                                   i * w, 1)
        return lax.fori_loop(0, n, block,
                             jnp.zeros((T, wlm.shape[1]), f32))

    with jax.default_matmul_precision("highest"):
        x = c["scale_emb"] * jnp.take(params["wte"], tokens,
                                      axis=0).astype(f32)
        for (kind, _), stack in zip(runs_of(c["layer_mixers"]),
                                    params["runs"]):
            mixer = sparse_attention if kind == ATTN else lightning

            def layer(x, lp, mixer=mixer):
                return ffn(mixer(x, lp), lp), None
            x, _ = lax.scan(layer, x, stack)
        x = rms(x, params["ln_f"]) / (c["hidden_size"] / c["dim_model_base"])
        return head(x)
