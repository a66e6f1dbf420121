"""Serve an LLM with KV-cache generation: batched decode on the
replica's chip, HTTP in front.

Run: JAX_PLATFORMS=cpu python examples/serve_llm.py
(toy-sized weights; the same deployment shape serves a real GPT —
replicas that request num_tpus=1 keep the params resident in HBM)
"""

import json
import urllib.request

import ray_tpu
from ray_tpu import serve


@serve.deployment(name="llm", num_replicas=1)
class LLM:
    def __init__(self):
        import jax
        import jax.numpy as jnp

        from ray_tpu.models import decode, gpt

        self.cfg = gpt.GPTConfig(vocab_size=256, d_model=64, n_heads=4,
                                 n_layers=2, d_ff=128, max_seq=128,
                                 dtype=jnp.float32, remat=False)
        self.params = gpt.init_params(self.cfg, jax.random.PRNGKey(0))
        self.decode = decode

    @serve.batch(max_batch_size=4, batch_wait_timeout_s=0.05)
    async def generate_batch(self, prompts):
        """Queries arriving together decode as ONE batched lax.scan —
        the MXU sees [batch, ...] matmuls instead of vector products.
        Mixed lengths left-pad to a common width; prompt_lens makes the
        pad columns invisible to attention, so batched results equal
        per-query results."""
        import jax.numpy as jnp
        width = max(len(p) for p in prompts)
        batch = jnp.asarray([[0] * (width - len(p)) + p
                             for p in prompts], jnp.int32)
        lens = jnp.asarray([len(p) for p in prompts], jnp.int32)
        out = self.decode.generate(self.params, batch, self.cfg,
                                   max_new_tokens=8, temperature=0.7,
                                   top_k=20, prompt_lens=lens)
        return [list(map(int, row)) for row in out]

    async def __call__(self, request):
        prompt = request.json()["tokens"]
        return {"generated": await self.generate_batch(prompt)}


def main():
    ray_tpu.init(num_cpus=4)
    serve.run(LLM, _start_proxy=True)
    addr = serve.get_proxy_address()
    url = f"http://{addr['host']}:{addr['port']}/llm"
    req = urllib.request.Request(
        url, data=json.dumps({"tokens": [1, 2, 3, 4]}).encode(),
        method="POST", headers={"content-type": "application/json"})
    with urllib.request.urlopen(req, timeout=60) as resp:
        out = json.loads(resp.read())
    print("generated:", out["generated"])
    assert len(out["generated"]) == 8
    serve.shutdown()
    ray_tpu.shutdown()


if __name__ == "__main__":
    main()
