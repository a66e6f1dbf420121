"""The plain reference of the decoder both configurations share: RoPE
(rotate-half), grouped-query attention, SwiGLU, RMSNorm, untied head —
written from the published description in straightforward float32
`jax.numpy`, matmul precision `highest`, no cache, no kernels, no
batching tricks.  It shares no code with `ray_tpu.models`.

It takes the SAME weights the program serves (bf16 values, in the
program's layout) and upcasts one layer at a time, so it fits beside a
serving replica.

Departures from the published models, all following the program so that
the two can be compared (listed in each configuration file too):
  * RMSNorm epsilon is the program's 1e-6 (`gpt._rmsnorm`), not the
    published 1e-5: LlamaConfig cannot express it.
  * InternLM2's fused `wqkv` is applied as separate q and kv
    projections: the same mathematics.
"""

from __future__ import annotations

RMS_EPS = 1e-6


def forward(params, tokens, c):
    """tokens [T] int32 -> logits [T, V] float32; full causal attention
    over the whole sequence.  `c` is the configuration file's dict."""
    import jax
    import jax.numpy as jnp
    from jax import lax

    f32 = jnp.float32
    n_heads, n_kv_heads = c["num_attention_heads"], c["num_key_value_heads"]
    rope_theta = float(c["rope_theta"])

    def rms(x, w):
        return x * lax.rsqrt(jnp.mean(x * x, -1, keepdims=True)
                             + RMS_EPS) * w.astype(f32)

    def rope(x):                                   # [T, h, d]
        T, _, d = x.shape
        half = d // 2
        inv = rope_theta ** (-jnp.arange(half, dtype=f32) / half)
        ang = jnp.arange(T, dtype=f32)[:, None] * inv[None, :]
        cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
        a, b = x[..., :half], x[..., half:]
        return jnp.concatenate([a * cos - b * sin, a * sin + b * cos], -1)

    def layer(x, lp):
        lp = jax.tree_util.tree_map(lambda w: w.astype(f32), lp)
        h = rms(x, lp["ln1"])
        q = rope(jnp.einsum("td,dhk->thk", h, lp["wq"]))
        kv = jnp.einsum("td,dchk->tchk", h, lp["wkv"])
        k, v = rope(kv[:, 0]), kv[:, 1]
        rep = n_heads // n_kv_heads
        k, v = jnp.repeat(k, rep, axis=1), jnp.repeat(v, rep, axis=1)
        T = x.shape[0]
        s = jnp.einsum("qhk,shk->hqs", q, k) * q.shape[-1] ** -0.5
        s = jnp.where(jnp.tril(jnp.ones((T, T), bool))[None], s, -jnp.inf)
        a = jnp.einsum("hqs,shk->qhk", jax.nn.softmax(s, -1), v)
        x = x + jnp.einsum("qhk,hkd->qd", a, lp["wo"])
        h = rms(x, lp["ln2"])
        x = x + (jax.nn.silu(h @ lp["w_gate"]) * (h @ lp["w_up"])) \
            @ lp["w_down"]
        return x, None

    with jax.default_matmul_precision("highest"):
        x = jnp.take(params["wte"], tokens, axis=0).astype(f32)
        x, _ = lax.scan(layer, x, params["blocks"])
        return rms(x, params["ln_f"]) @ params["wlm"].astype(f32)
