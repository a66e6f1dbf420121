"""The plain reference of the GPT block: token and learned position
embeddings, RMSNorm (epsilon 1e-6) before each branch, one fused
query/key/value projection, causal attention with as many KV heads as
heads, a two-matrix feed-forward with the tanh form of GELU, a final
RMSNorm and an untied head — float32 `jax.numpy`, matmul precision
`highest`, no cache.  Written from that description; it shares no code
with the program.
"""

from __future__ import annotations

RMS_EPS = 1e-6


def forward(params, tokens, c):
    """tokens [T] int32 -> logits [T, V] float32."""
    import jax
    import jax.numpy as jnp
    from jax import lax

    f32 = jnp.float32
    T = tokens.shape[0]

    def rms(x, w):
        return x * lax.rsqrt(jnp.mean(x * x, -1, keepdims=True)
                             + RMS_EPS) * w.astype(f32)

    def gelu(x):
        return 0.5 * x * (1 + jnp.tanh(
            (2 / jnp.pi) ** 0.5 * (x + 0.044715 * x ** 3)))

    def layer(x, lp):
        lp = jax.tree_util.tree_map(lambda w: w.astype(f32), lp)
        qkv = jnp.einsum("td,dchk->tchk", rms(x, lp["ln1"]), lp["wqkv"])
        q, k, v = qkv[:, 0], qkv[:, 1], qkv[:, 2]
        s = jnp.einsum("qhk,shk->hqs", q, k) * c["head_dim"] ** -0.5
        s = jnp.where(jnp.tril(jnp.ones((T, T), bool))[None], s, -jnp.inf)
        a = jnp.einsum("hqs,shk->qhk", jax.nn.softmax(s, -1), v)
        x = x + jnp.einsum("qhk,hkd->qd", a, lp["wo"])
        x = x + gelu(rms(x, lp["ln2"]) @ lp["w1"]) @ lp["w2"]
        return x, None

    with jax.default_matmul_precision("highest"):
        x = (jnp.take(params["wte"], tokens, axis=0)
             + params["wpe"][:T]).astype(f32)
        x, _ = lax.scan(layer, x, params["blocks"])
        return rms(x, params["ln_f"]) @ params["wlm"].astype(f32)
