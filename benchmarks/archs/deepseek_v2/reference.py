"""The plain reference of DeepSeek-V2, from the equations: float32
`jax.numpy`, matmul precision `highest`, no cache, no pages, no grouped
matmul.  It shares no code with `ray_tpu.models` and imports jax alone.

It takes the SAME weights the program serves (bf16 values, the program's
layout: a tuple of layers) and upcasts a matrix, or one expert, at a
time: attention is computed in blocks of queries, the dense feed-forward
and the output head in blocks of columns, the experts one by one, so
3,080 positions fit beside a serving replica's 10 GB of weights and its
cache.

The equations (`c` is the configuration file's dict; RMSNorm eps 1e-6):

  attention (MLA), in the EXPANDED form at every position:
    q = q_b(rms(q_a(x))), 128 heads of 128 (nope) + 64 (rope);
    [c_kv ; k_pe] = kv_a(x), c_kv (512) normed, k_pe (64) shared by all
    heads; k_nope = c_kv Wk_b, v = c_kv Wv_b (kv_b_proj's two halves);
    RoPE on q_pe and k_pe with YaRN's frequencies (beta_fast 32,
    beta_slow 1, factor 40 over 4096; its cos/sin factor is
    mscale / mscale_all_dim = 1); scores (q_nope . k_nope + q_pe . k_pe)
    x 192^-0.5 x (0.1 mscale_all_dim ln factor + 1)^2; causal softmax;
    o = P v, then o_proj.

  feed-forward: SwiGLU of width 12288 in the first `first_k_dense_replace`
  layers.  After them: p = softmax(x W_r) over ALL published experts in
  float32; a group (of n_group) scores its best expert; the topk_group
  best groups are kept; the num_experts_per_tok best experts inside them
  are chosen; their weights are p itself (norm_topk_prob false) times
  routed_scaling_factor.  The layer adds shared(x), one SwiGLU of
  n_shared_experts x 1536, and the sum over chosen experts of
  weight x SwiGLU_e(x) — OVER THE EXPERTS HELD HERE ONLY: experts
  `expert_offset` .. `expert_offset + n_routed_experts - 1` of the
  published count.  What the others would add is left out, as in the
  program: that partial sum is what goes on to the next layer.

`c` may carry switches that only tools/dsv2_limits.py and the tests
write (`_top_k`, `_no_group_limit`, `_routed_scale`, `_no_shared`,
`_no_yarn_scale`): the controls a comparison must catch.
"""

from __future__ import annotations

import math

RMS_EPS = 1e-6


def yarn_inv_freq(c):
    """[qk_rope_head_dim / 2] rotary frequencies under YaRN."""
    import jax.numpy as jnp

    rs = c["rope_scaling"]
    dim, base = c["qk_rope_head_dim"], float(c["rope_theta"])
    span = rs["original_max_position_embeddings"]

    def dim_turning(rotations):
        return dim * math.log(span / (rotations * 2 * math.pi)) \
            / (2 * math.log(base))
    low = max(math.floor(dim_turning(rs["beta_fast"])), 0)
    high = min(math.ceil(dim_turning(rs["beta_slow"])), dim - 1)
    if low == high:
        high += 0.001
    i = jnp.arange(dim // 2, dtype=jnp.float32)
    ramp = jnp.clip((i - low) / (high - low), 0.0, 1.0)
    plain = base ** (-2.0 * i / dim)
    return plain / rs["factor"] * ramp + plain * (1.0 - ramp)


def yarn_mscale(factor, mscale):
    return 1.0 if factor <= 1 else 0.1 * mscale * math.log(factor) + 1.0


def softmax_scale(c):
    rs = c["rope_scaling"]
    scale = (c["qk_nope_head_dim"] + c["qk_rope_head_dim"]) ** -0.5
    if c.get("_no_yarn_scale"):
        return scale
    return scale * yarn_mscale(rs["factor"], rs["mscale_all_dim"]) ** 2


def chosen_experts(p, c):
    """p [N, E] float32 router scores -> (ids [N, k], weights [N, k]):
    group-limited greedy top-k, weights the scores times the factor."""
    import jax.numpy as jnp
    from jax import lax

    N, E = p.shape
    G = c["n_group"]
    k = c.get("_top_k", c["num_experts_per_tok"])
    if not c.get("_no_group_limit"):
        best = p.reshape(N, G, E // G).max(-1)
        kept = lax.top_k(best, c["topk_group"])[1]
        in_kept = jnp.zeros((N, G), bool).at[
            jnp.arange(N)[:, None], kept].set(True)
        p = jnp.where(jnp.repeat(in_kept, E // G, axis=1), p, 0.0)
    w, ids = lax.top_k(p, k)
    return ids, w * c.get("_routed_scale", c["routed_scaling_factor"])


def moe(h, lp, c, lo=lambda a: a, with_routes=False):
    """The expert layer on normed h [T, D] float32: shared(h) + the held
    experts' part, each held expert applied to every token under a mask.
    `lp` the layer's weights; the held experts are the published ones
    `expert_offset` .. + `n_routed_experts`."""
    import jax
    import jax.numpy as jnp
    from jax import lax

    f32 = jnp.float32
    E_all = c["published"]["n_routed_experts"]
    held, first = c["n_routed_experts"], c.get("expert_offset", 0)
    p = jax.nn.softmax(h @ lp["router"].astype(f32), axis=-1)
    if p.shape[1] != E_all:
        raise ValueError(f"the router scores {p.shape[1]} experts, the "
                         f"published count is {E_all}")
    ids, w = chosen_experts(p, c)
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
    setting the comparison's limits (tools/dsv2_limits.py), never for a
    judged run.  `with_routes` also returns the chosen expert ids
    [expert layers, T, k]."""
    import jax
    import jax.numpy as jnp
    from jax import lax

    f32 = jnp.float32
    H = c["num_attention_heads"]
    dn, dr, dv = (c["qk_nope_head_dim"], c["qk_rope_head_dim"],
                  c["v_head_dim"])
    kr = c["kv_lora_rank"]
    rs = c["rope_scaling"]
    T = tokens.shape[0]
    qb = min(query_block, T)
    n_qb = -(-T // qb)
    Tp = n_qb * qb
    positions = jnp.arange(T)
    scale = softmax_scale(c)

    def lo(a):
        a = a.astype(f32)
        if round_to is None:
            return a
        # a saturating cast: an 8-bit float has no infinity
        top = float(jnp.finfo(round_to).max)
        return jnp.clip(a, -top, top).astype(round_to).astype(f32)

    def rms(x, w):
        return x * lax.rsqrt(jnp.mean(x * x, -1, keepdims=True)
                             + RMS_EPS) * w.astype(f32)

    inv_freq = yarn_inv_freq(c)
    m = yarn_mscale(rs["factor"], rs["mscale"]) \
        / yarn_mscale(rs["factor"], rs["mscale_all_dim"])

    def rope(x):                                   # [T, ..., d]
        half = x.shape[-1] // 2
        ang = positions.astype(f32)[:, None] * inv_freq[None, :]
        ang = ang.reshape((T,) + (1,) * (x.ndim - 2) + (half,))
        cos, sin = jnp.cos(ang) * m, jnp.sin(ang) * m
        a, b = x[..., :half], x[..., half:]
        return jnp.concatenate([a * cos - b * sin, a * sin + b * cos], -1)

    def column_blocks(width):
        n = width_blocks if width % width_blocks == 0 else 1
        return n, width // n

    def attention(x, lp):
        h = rms(x, lp["ln1"])
        qa = rms(lo(h) @ lo(lp["wq_a"]), lp["q_norm"])
        q = (lo(qa) @ lo(lp["wq_b"])).reshape(T, H, dn + dr)
        kva = lo(h) @ lo(lp["wkv_a"])
        ckv = rms(kva[:, :kr], lp["kv_norm"])
        k_pe = rope(kva[:, kr:])                           # [T, dr]
        q_nope, q_pe = q[..., :dn], rope(q[..., dn:])
        k_nope = jnp.einsum("sc,hnc->shn", lo(ckv), lo(lp["wk_b"]))
        v = jnp.einsum("sc,hcv->shv", lo(ckv), lo(lp["wv_b"]))

        def attend(qn, qp, pp):                # [qb, H, dn], [qb, H, dr]
            s = (jnp.einsum("qhn,shn->qhs", qn, k_nope)
                 + jnp.einsum("qhr,sr->qhs", qp, k_pe)) * scale
            seen = positions[None, :] <= pp[:, None]
            s = jnp.where(seen[:, None, :], s, -jnp.inf)
            return jnp.einsum("qhs,shv->qhv", jax.nn.softmax(s, -1), v)

        cut = [jnp.pad(a, [(0, Tp - T)] + [(0, 0)] * (a.ndim - 1)
                       ).reshape((n_qb, qb) + a.shape[1:])
               for a in (q_nope, q_pe, positions)]
        o = lax.map(lambda args: attend(*args), tuple(cut))
        o = o.reshape(Tp, H, dv)[:T]
        return x + jnp.einsum("thv,hvd->td", lo(o), lo(lp["wo"]))

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
        for lp in params["layers"]:
            x = attention(x, lp)
            h = rms(x, lp["ln2"])
            if "router" in lp:
                out, ids = moe(h, lp, c, lo, with_routes=True)
                routes.append(ids)
            else:
                out = dense_ffn(h, lp)
            x = x + out
        logits = head(rms(x, params["ln_f"]))
    return (logits, jnp.stack(routes)) if with_routes else logits
