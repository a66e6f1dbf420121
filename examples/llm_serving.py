"""Continuous-batching LLM serving: one engine, many concurrent
requests, tokens streamed as they are generated.

Run: JAX_PLATFORMS=cpu python examples/llm_serving.py

Contrast with serve_llm.py (request-level @serve.batch): here requests
are batched at ITERATION level — a request joins the running decode
batch the moment a KV slot frees, streams each token immediately, and
leaves without waiting for anyone else (ray_tpu.serve.llm).  Toy-sized
weights; the same deployment shape serves a real GPT (replicas that
request num_tpus=1 keep params + the KV slot pool resident in HBM).
"""

import json
import time
import urllib.request

import ray_tpu
from ray_tpu import serve
from ray_tpu.serve.llm import llm_deployment


def load_model():
    """Zero-arg loader, run INSIDE the replica (weights never ride the
    deployment pickle)."""
    import jax
    import jax.numpy as jnp

    from ray_tpu.models import gpt

    cfg = gpt.GPTConfig(vocab_size=256, d_model=64, n_heads=4,
                        n_layers=2, d_ff=128, max_seq=128,
                        dtype=jnp.float32, remat=False)
    return gpt.init_params(cfg, jax.random.PRNGKey(0)), cfg


def main():
    ray_tpu.init(num_cpus=4)
    serve.start()
    handle = llm_deployment(
        load_model,
        # KV memory is a PAGED pool: admission is bounded by free pages
        # (kv_pages * page_size tokens), identical prompt prefixes share
        # pages through the radix cache, and speculate_k fuses
        # prompt-lookup speculation into the batched decode tick.
        engine_config={"num_slots": 4, "max_seq": 64, "page_size": 8,
                       "kv_pages": 32, "speculate_k": 3,
                       "prefill_chunk": 16, "max_queue_len": 32},
        default_generation={"max_new_tokens": 12},
    ).deploy()

    # Unary: several concurrent calls share the decode batch.
    prompts = [[1, 2, 3, 4], [9, 8, 7], [5, 5, 5, 5, 5]]
    resps = [handle.generate.remote(p) for p in prompts]
    for p, r in zip(prompts, resps):
        print("generate", p, "->", r.result(timeout=120))

    # Streaming: tokens arrive one by one, long before the request
    # finishes (the method is named "stream", which shadows
    # DeploymentHandle.stream — hence options()).
    t0 = time.monotonic()
    for tok in handle.options("stream").stream([1, 2, 3, 4],
                                               max_new_tokens=12):
        print(f"  streamed token {tok} at +{time.monotonic() - t0:.3f}s")

    # HTTP: plain JSON and SSE on the same route.
    serve.run(serve.get_deployment("llm"), _start_proxy=True)
    addr = serve.get_proxy_address()
    url = f"http://{addr['host']}:{addr['port']}/llm"
    req = urllib.request.Request(
        url, data=json.dumps({"tokens": [1, 2, 3, 4]}).encode(),
        method="POST", headers={"content-type": "application/json"})
    with urllib.request.urlopen(req, timeout=60) as resp:
        print("HTTP JSON:", json.loads(resp.read()))

    req = urllib.request.Request(
        url, data=json.dumps({"tokens": [1, 2, 3, 4]}).encode(),
        method="POST", headers={"content-type": "application/json",
                                "accept": "text/event-stream"})
    events = []
    with urllib.request.urlopen(req, timeout=60) as resp:
        print("HTTP SSE:", resp.headers["Content-Type"])
        for line in resp:
            line = line.strip()
            if line.startswith(b"data: "):
                events.append(line[6:].decode())
    print("SSE events:", events)
    assert events[-1] == "[DONE]" and len(events) == 13

    print("engine stats:", handle.stats.remote().result(timeout=60))
    serve.shutdown()
    ray_tpu.shutdown()
    print("llm serving example done")


if __name__ == "__main__":
    main()
