"""The plain reference of GLM-5 (`glm_moe_dsa`), from the equations:
float32 `jax.numpy`, matmul precision `highest`, no cache, no pages, no
chunks, no gather, no grouped matmul.  It shares no code with
`ray_tpu.models` or `ray_tpu.ops` and imports jax alone.

It takes the SAME weights the program serves (bf16 values, the program's
layout: a tuple of layers) and upcasts a matrix, or one expert, at a
time; attention is taken in blocks of queries and the head in blocks of
positions, so that `[heads, T, T]` never stands whole beside a serving
replica.  `c` is the configuration file's dict.  A layer is pre-norm
(RMSNorm, eps `rms_norm_eps`): x = x + mixer(rms(x)); x = x +
ffn(rms(x)); after the last layer rms, then the untied head.  `h` is the
mixer's normed input.

  mixer (H heads, ranks rq / rk, dn + dr a query head, dv a value head,
  theta `rope_parameters.rope_theta`, no scaling): cq = rms(h Wqa);
  [ckv~ | kr~] = h Wkva, ckv = rms(ckv~), kr = rope(kr~), one for all
  heads; q = cq Wqb -> [H, dn | dr], the dr part rotated; k_h,s =
  [ckv_s Wkb_h | kr_s], v_h,s = ckv_s Wvb_h, EXPANDED at every position.
  RoPE pairs (2i, 2i + 1) (`rope_interleave`).
  Indexer: qi = cq Wiq -> [32, 128]; ki = layernorm(h Wik) [128] (gain
  and bias, eps 1e-6); RoPE on the first `qk_rope_head_dim` lanes of
  each, pairs (2i, 2i + 1) (`indexer_rope_interleave`); wi = (h Wiw) x
  32^-0.5 x 128^-0.5;
      I(t, s) = sum_j wi_t,j relu(qi_t,j . ki_s),  s <= t,
  AS AN EXPLICIT [T, T] SCORE under the causal mask; S_t = the positions
  `lax.top_k` names, the min(t + 1, index_topk) largest of row t; the
  attention's softmax runs over S_t alone, as a 0/1 mask:
      o_t,h = sum_{s in S_t} softmax_s(q_t,h . k_h,s / sqrt(dn + dr))
              v_h,s;
  mixer = concat_h(o_t,h) Wo.

  expert layer: s = sigmoid(h2 Wr) over ALL published experts, float32;
  the num_experts_per_tok largest of s + bias are chosen; weights the
  UNBIASED s renormalised over the chosen, times routed_scaling_factor.
  The layer adds shared(h2), one SwiGLU of n_shared_experts x
  moe_intermediate_size, and the sum over chosen experts of weight x
  SwiGLU_e(h2) - OVER THE EXPERTS HELD HERE ONLY (`expert_offset` .. +
  `n_routed_experts` - 1 of the published count), each held expert
  applied to every token under a mask.  A layer whose index is below
  `first_k_dense_replace`: one SwiGLU of `intermediate_size`.

`c` may carry switches that only tools/glm5_limits.py and the tests
write (SWITCHES): the controls a comparison must catch.
"""

from __future__ import annotations

import math

INDEX_NORM_EPS = 1e-6

# what a control changes, by the key it sets in `c`
SWITCHES = (
    "_no_selection",      # a layer attends to every key it sees
    "_index_topk",        # this many keys chosen, not index_topk
    "_head_weights_one",  # the indexer's head weights w set to 1
    "_no_relu",           # the indexer's products summed without ReLU
    "_no_index_bias",     # the indexer key's LayerNorm bias left out
    "_no_index_rope",     # the indexer's queries and keys not rotated
    "_no_router_bias",    # the selection bias left out of the choice
    "_routed_scaling_factor",   # this factor, not routed_scaling_factor
    "_top_k", "_no_rope")


def chosen_experts(s, bias, c):
    """s [N, E] float32 sigmoid scores -> (ids [N, k], weights [N, k])."""
    import jax.numpy as jnp
    from jax import lax

    k = c.get("_top_k", c["num_experts_per_tok"])
    by = s if c.get("_no_router_bias") else s + bias[None]
    ids = lax.top_k(by, k)[1]
    w = jnp.take_along_axis(s, ids, axis=1)
    return ids, w / w.sum(-1, keepdims=True) * c.get(
        "_routed_scaling_factor", c["routed_scaling_factor"])


def moe(h, lp, c, lo=lambda a: a, with_routes=False, with_shared=True):
    """The expert layer on normed h [T, D] float32: shared(h) + the held
    experts' part.  `with_shared` False leaves the shared expert out (a
    test adds the routed parts of all 16 shares and the shared one
    once)."""
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


def forward(params, tokens, c, query_block=128, round_to=None,
            with_routes=False):
    """tokens [T] int32 -> logits [T, V] float32.  `round_to` (a dtype
    name) rounds both inputs of every weight matmul to that type first:
    the reference in a lower precision, for setting the comparison's
    limits (tools/glm5_limits.py), never for a judged run.
    `with_routes` also returns the chosen expert ids [expert layers, T,
    k] and every layer's chosen keys as 0/1 masks [layers, T, T]."""
    import jax
    import jax.numpy as jnp
    from jax import lax

    f32 = jnp.float32
    eps = float(c["rms_norm_eps"])
    theta = float(c["rope_parameters"]["rope_theta"])
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

    def rope(x, off=False):                           # [T, ..., d]
        if off:
            return x
        half = x.shape[-1] // 2
        inv = theta ** (-jnp.arange(half, dtype=f32) / half)
        ang = positions.astype(f32)[:, None] * inv[None, :]
        ang = ang.reshape((T,) + (1,) * (x.ndim - 2) + (half,))
        cos, sin = jnp.cos(ang), jnp.sin(ang)
        a, b = x[..., 0::2], x[..., 1::2]
        return jnp.stack([a * cos - b * sin, a * sin + b * cos],
                         -1).reshape(x.shape)

    qb = min(query_block, T)
    n_qb = -(-T // qb)
    Tp = n_qb * qb

    def blocks(*arrays):
        """Each [T, ...] padded to whole query blocks, [n_qb, qb, ...]."""
        return tuple(jnp.pad(a, [(0, Tp - T)] + [(0, 0)] * (a.ndim - 1)
                             ).reshape((n_qb, qb) + a.shape[1:])
                     for a in arrays)

    H = c["num_attention_heads"]
    rk = c["kv_lora_rank"]
    dn, dr, dv = c["qk_nope_head_dim"], c["qk_rope_head_dim"], c["v_head_dim"]
    Hi, di = c["index_n_heads"], c["index_head_dim"]
    topk = min(c.get("_index_topk", c["index_topk"]), T)
    no_rope = bool(c.get("_no_rope"))
    masks = []

    def mixer(x, lp):
        h = rms(x, lp["ln1"])
        cq = rms(lo(h) @ lo(lp["wq_a"]), lp["q_norm"])
        kva = lo(h) @ lo(lp["wkv_a"])
        ckv = rms(kva[:, :rk], lp["kv_norm"])
        k_pe = rope(kva[:, rk:], no_rope)                   # [T, dr]
        q = (lo(cq) @ lo(lp["wq_b"])).reshape(T, H, dn + dr)
        q_nope, q_pe = q[..., :dn], rope(q[..., dn:], no_rope)
        k_nope = jnp.einsum("sc,hnc->shn", lo(ckv), lo(lp["wk_b"]))
        v = jnp.einsum("sc,hcv->shv", lo(ckv), lo(lp["wv_b"]))

        qi = jnp.einsum("tr,rhd->thd", lo(cq), lo(lp["wiq"]))
        ki = lo(h) @ lo(lp["wik"])
        mean = ki.mean(-1, keepdims=True)
        ki = (ki - mean) * lax.rsqrt(
            ((ki - mean) ** 2).mean(-1, keepdims=True) + INDEX_NORM_EPS
        ) * lp["ik_norm"].astype(f32)
        if not c.get("_no_index_bias"):
            ki = ki + lp["ik_bias"].astype(f32)
        off = no_rope or bool(c.get("_no_index_rope"))
        turn = lambda a: jnp.concatenate(  # noqa: E731
            [rope(a[..., :dr], off), a[..., dr:]], -1)
        qi, ki = turn(qi), turn(ki)
        wi = (lo(h) @ lo(lp["wiw"])) * (Hi ** -0.5 * di ** -0.5)
        if c.get("_head_weights_one"):
            wi = jnp.ones_like(wi)

        def attend(qn, qp, pp, qi, wi):
            causal = positions[None, :] <= pp[:, None]
            seen = causal
            if not c.get("_no_selection"):
                prod = jnp.einsum("qhd,sd->qhs", lo(qi), lo(ki))
                if not c.get("_no_relu"):
                    prod = jax.nn.relu(prod)
                score = (prod * wi[:, :, None]).sum(1)        # [qb, T]
                best, idx = lax.top_k(
                    jnp.where(causal, score, -jnp.inf), topk)
                seen = jnp.zeros((qb, T), bool).at[
                    jnp.arange(qb)[:, None], idx].set(jnp.isfinite(best))
            s = (jnp.einsum("qhn,shn->qhs", qn, k_nope)
                 + jnp.einsum("qhr,sr->qhs", qp, k_pe)) / math.sqrt(dn + dr)
            s = jnp.where(seen[:, None, :], s, -jnp.inf)
            return jnp.einsum("qhs,shv->qhv", jax.nn.softmax(s, -1), v), seen

        o, seen = lax.map(lambda args: attend(*args),
                          blocks(q_nope, q_pe, positions, qi, wi))
        o = o.reshape(Tp, H, dv)[:T]
        if with_routes:
            masks.append(seen.reshape(Tp, T)[:T])
        return x + jnp.einsum("thv,hvd->td", lo(o), lo(lp["wo"]))

    routes = []
    with jax.default_matmul_precision("highest"):
        x = jnp.take(params["wte"], tokens, axis=0).astype(f32)
        for i, lp in enumerate(params["layers"]):
            x = mixer(x, lp)
            h = rms(x, lp["ln2"])
            if i < c["first_k_dense_replace"]:
                out = swiglu(h, lp)
            else:
                out, ids = moe(h, lp, c, lo, with_routes=True)
                routes.append(ids)
            x = x + out
        wlm = lo(params["wlm"])
        logits = lax.map(lambda xb: lo(xb) @ wlm,
                         blocks(rms(x, params["ln_f"]))[0])
        logits = logits.reshape(Tp, -1)[:T]
    if with_routes:
        return logits, jnp.stack(routes), jnp.stack(masks)
    return logits
