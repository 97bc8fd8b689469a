"""Online HTTP serving: ServingServer over a continuous-batching engine.

The reference's only inference path is offline Spark ``mapPartitions``
prediction (``elephas/spark_model.py:235-272``); this example runs the
TPU framework's online half end to end — an HTTP server whose device
batch interleaves concurrent client requests, with per-request sampling
settings and cancellation on the wire.

Run: JAX_PLATFORMS=cpu python examples/http_serving.py
"""
import json
import os
import sys
import threading
import urllib.request

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax
import jax.numpy as jnp
import numpy as np

from elephas_tpu import DecodeEngine, ServingServer
from elephas_tpu.models.transformer import (TransformerConfig, generate,
                                            init_params)
from elephas_tpu.utils.text import ByteTokenizer

tok = ByteTokenizer()
config = TransformerConfig(vocab_size=tok.vocab_size, num_layers=2,
                           num_heads=4, d_model=64, d_ff=128,
                           max_seq_len=96, dtype=jnp.float32)
params = init_params(config, jax.random.PRNGKey(0))


def post(port, path, payload):
    req = urllib.request.Request(
        f"http://127.0.0.1:{port}{path}",
        data=json.dumps(payload).encode(),
        headers={"Content-Type": "application/json"})
    return json.loads(urllib.request.urlopen(req, timeout=120).read())


# prefix caching pins the shared "system prompt", and the paged block
# pool holds HALF the contiguous cache's positions (4 slots x 96 = 384
# vs 23 allocatable blocks x 8 = 184): admission queues when the pool
# runs dry, blocks recycle on retirement
engine = DecodeEngine(params, config, max_slots=4, paged=(24, 8))
system = tok.encode("SYSTEM: ")
engine.register_prefix(system)

with ServingServer(engine, tokenizer=tok) as srv:
    print(f"serving on 127.0.0.1:{srv.port}")

    prompts = ["SYSTEM: hello", "SYSTEM: goodbye", "SYSTEM: what",
               "plain prompt", "SYSTEM: again"]
    results = {}

    def client(i):
        results[i] = post(srv.port, "/v1/generate",
                          {"text": prompts[i], "max_new_tokens": 16})

    threads = [threading.Thread(target=client, args=(i,))
               for i in range(len(prompts))]
    for t in threads:
        t.start()
    for t in threads:
        t.join()

    for i, text in enumerate(prompts):
        ref = list(np.asarray(generate(
            params, jnp.asarray(tok.encode(text))[None], 16, config))[0])
        assert results[i]["tokens"] == ref, f"client {i} diverged"
    stats = post(srv.port, "/v1/submit",
                 {"text": "one more", "max_new_tokens": 4}) and \
        json.loads(urllib.request.urlopen(
            f"http://127.0.0.1:{srv.port}/stats", timeout=120).read())
    print(f"{len(prompts)} concurrent clients ≡ solo decode; "
          f"prefix hits {stats.get('prefix_hits', 0)}, "
          f"tokens/step {stats['tokens_per_step']:.2f}")
print("server stopped cleanly")
