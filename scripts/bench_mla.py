"""On-chip MLA decode throughput: latent Pallas kernel vs XLA gathers.

Measures fused decode windows (llama.decode_window) on a 1B-class
dense-MLA config (DeepSeek head geometry: kv_lora 512, rope 64, 16
heads) for the two paths the engine can take:

  * xla      — absorbed XLA decode (full-table gathers + 2L scatters)
  * merged   — latent kernel + flash merge + ONE batched append
               (kernels on: TPU and kv_lora_rank % 128 == 0)

Prints one JSON line per path.
"""

import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax

from dynamo_tpu.models import llama
from dynamo_tpu.models.config import ModelConfig


def main() -> None:
    on_cpu = jax.devices()[0].platform == "cpu"
    if on_cpu:
        print(json.dumps({"metric": "bench_mla_skipped_cpu", "value": 0}))
        return
    from bench import time_decode_windows

    cfg = ModelConfig(
        vocab_size=32768, hidden_size=2048, intermediate_size=8192,
        num_layers=16, num_heads=16, num_kv_heads=16,
        max_position_embeddings=2048, dtype="bfloat16",
        kv_lora_rank=512, qk_nope_head_dim=128, qk_rope_head_dim=64,
        v_head_dim=128,
    )
    B, BLOCK, CTX, WINDOW = 16, 16, 2048, 16
    params = llama.init_params(cfg, jax.random.key(0))
    param_bytes = sum(
        x.size * x.dtype.itemsize for x in jax.tree.leaves(params)
    )
    roofline = 819e9 / param_bytes * B  # v5e HBM bw / weight stream

    for label, up in {"xla": False, "merged": True}.items():
        try:
            tps = time_decode_windows(
                params, cfg, B=B, BLOCK=BLOCK, CTX=CTX, WINDOW=WINDOW,
                use_pallas=up, iters=800 // WINDOW,
            ) / jax.device_count()  # per-chip, same as bench.py
            print(json.dumps({
                "metric": f"mla1b_decode_tokens_per_sec_per_chip_{label}",
                "value": round(tps, 2),
                "unit": "tokens/s/chip",
                "vs_baseline": round(tps / roofline, 4),
            }), flush=True)
        except Exception as e:  # noqa: BLE001 — record, keep measuring
            print(json.dumps({
                "metric": f"mla1b_decode_{label}_error",
                "value": 0,
                "error": f"{type(e).__name__}: {e}"[:300],
            }), flush=True)


if __name__ == "__main__":
    main()
