"""``closed_loop``: ``clients`` callers, each of which sends its next
request when the last one finishes.

Prompt and output lengths are the midpoints of ``strata`` (default 8)
equal-probability strata of the mix's log-uniform ``prompt_tokens`` and
``output_tokens``.  Requests come in blocks of ``strata``: each block
holds every prompt length once and every output length once, paired at
random.  Request ``r`` of client ``c`` is request ``r * clients + c`` of
the stream, so the first requests of all clients form the first
block(s).  Prompt tokens are uniform over the vocabulary.
"""

import numpy as np

from lib import traffic


def generate(mix: dict, seed: int, vocab: int) -> dict:
    rng = traffic.rng_for(seed, 1)
    k = int(mix.get("strata", 8))
    prompts = traffic.strata(mix["prompt_tokens"], k)
    outs = traffic.strata(mix["output_tokens"], k)
    clients, per_client = int(mix["clients"]), int(mix["requests_per_client"])
    stream = []
    while len(stream) < clients * per_client:
        for i, j in zip(rng.permutation(k), rng.permutation(k)):
            prompt = rng.integers(0, vocab, prompts[i], dtype=np.int32)
            stream.append((prompt, outs[j]))
    return {"streams": [[stream[r * clients + c] for r in range(per_client)]
                        for c in range(clients)]}
