"""LSH-accelerated decode attention demo (paper §I: LSH for LLM inference).

The KV cache is an index (``repro.decode.KVCacheIndex``): prefill builds
per-(batch, kv-head) DE-Forests over the MIPS-augmented keys through the
fused build pipeline, then a multi-step decode loop runs — every step
upserts its new key into the streaming delta (live KV growth), retrieval
is a batched fused ``range_rerank`` query, and exact attention runs over
the retrieved ∪ window ∪ sink survivor set.

  PYTHONPATH=src python examples/lsh_attention_decode.py
"""

import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.decode import KVCacheIndex, KVSpec, LSHDecoder
from repro.launch import compile_cache
from repro.models import layers as L


def main():
    compile_cache.enable()
    rng = np.random.default_rng(0)
    b, S, hk, g, dh = 1, 4096, 4, 4, 64
    h = hk * g
    steps, prefill_len = 24, S - 32
    print(f"cache: {S} slots x {hk} kv heads x {dh} dims; "
          f"prefill {prefill_len}, decode {steps} steps")

    k_cache = jnp.asarray(rng.standard_normal((b, S, hk, dh)).astype(
        np.float32) * 0.3)
    v_cache = jnp.asarray(rng.standard_normal((b, S, hk, dh)).astype(
        np.float32))

    t0 = time.perf_counter()
    index = KVCacheIndex.prefill(k_cache[:, :prefill_len], jax.random.key(0),
                                 KVSpec(delta_capacity=64, m_top=64,
                                        max_rounds=6))
    jax.block_until_ready(index.forest.points_sorted)
    print(f"KV index prefilled in {time.perf_counter() - t0:.2f}s "
          f"({index.n_points} positions, "
          f"{index.index_size_bytes() / 2 ** 20:.1f} MiB)")

    decoder = LSHDecoder(index, window=64, sinks=4, refresh_every=4)
    cos_all = []
    planted = 0
    for t in range(steps):
        length = prefill_len + t + 1
        # query attends strongly to a planted earlier position; the target
        # moves at refresh boundaries (between refreshes the cached
        # candidate table serves the drifting-slowly query regime)
        if t % decoder.refresh_every == 0:
            planted = int(rng.integers(0, prefill_len))
        q = np.repeat(np.asarray(k_cache[:, planted])[:, :, None, :], g, 2)
        q = jnp.asarray((q * 16).reshape(b, 1, h, dh))
        k_new = k_cache[:, length - 1]                     # (b, hk, dh)

        out_lsh = decoder.step(q, k_cache, v_cache, k_new, length)
        out_full = L.decode_gqa_attention(q, k_cache, v_cache, length)
        a = np.asarray(out_lsh).reshape(-1)
        f = np.asarray(out_full).reshape(-1)
        cos_all.append(float(a @ f / (np.linalg.norm(a)
                                      * np.linalg.norm(f) + 1e-9)))

    m = index.spec.m_top + index.spec.delta_capacity + 64 + 4
    print(f"decoded {steps} steps with {decoder.n_refreshes} retrievals "
          f"(refresh_every={decoder.refresh_every}), "
          f"{index.delta.count} keys in the delta")
    print(f"positions attended per head <= {m}/{prefill_len + steps} "
          f"({100 * m / (prefill_len + steps):.1f}%)")
    print(f"cosine(lsh_decode, exact): mean={np.mean(cos_all):.4f} "
          f"min={np.min(cos_all):.4f}")


if __name__ == "__main__":
    main()
