"""Serve a jax model over HTTP with batching and an ASGI ingress.

Run: JAX_PLATFORMS=cpu python examples/serve_model.py
"""

import json
import urllib.request

import ray_tpu
from ray_tpu import serve


def main():
    ray_tpu.init(num_cpus=4)

    @serve.deployment(name="scorer", num_replicas=1)
    class Scorer:
        def __init__(self):
            import jax
            import jax.numpy as jnp
            k = jax.random.PRNGKey(0)
            self.w = jax.random.normal(k, (4, 2))
            self.fwd = jax.jit(lambda w, x: jnp.argmax(x @ w, -1))

        @serve.batch(max_batch_size=8, batch_wait_timeout_s=0.02)
        async def score_batch(self, xs):
            import jax.numpy as jnp
            batch = jnp.stack([jnp.asarray(x, jnp.float32) for x in xs])
            return [int(v) for v in self.fwd(self.w, batch)]

        async def __call__(self, request):
            x = request.json()["x"]
            return {"class": await self.score_batch(x)}

    handle = serve.run(Scorer, _start_proxy=True)
    addr = serve.get_proxy_address()
    url = f"http://{addr['host']}:{addr['port']}/scorer"
    req = urllib.request.Request(
        url, data=json.dumps({"x": [1.0, 0.0, -1.0, 0.5]}).encode(),
        method="POST", headers={"content-type": "application/json"})
    with urllib.request.urlopen(req, timeout=30) as resp:
        print("HTTP:", json.loads(resp.read()))

    # Same deployment through a Python handle (no HTTP hop):
    from ray_tpu.serve import Request
    out = handle.remote(Request(
        method="POST", body=json.dumps({"x": [0.0, 1.0, 0.0, 0.0]})
        .encode())).result(timeout=30)
    print("handle:", out)

    serve.shutdown()
    ray_tpu.shutdown()


if __name__ == "__main__":
    main()
