"""``gigachat3.5-432b-a28b``'s plain reference against the program's
forward at tiny size (``testdata/tiny-gigachat35``: L L L A L, one dense
FFN then four expert layers that hold 4 of 16 experts from the 4th, 4 a
token, a shared expert, a sigmoid router with a selection bias, an untied
head). (The rehearsal, ``run.py --rehearse``, compares it with the SERVED
engine; tier-1's ``tests/test_gigachat35.py`` with the step programs and
``tests/test_gigachat35_engine.py`` with the engine's state handling.)"""

import json
import os

import jax
import jax.numpy as jnp
import numpy as np

from chipbench import reference

HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
CONFIG = os.path.join(HERE, "configs", "gigachat3.5-432b-a28b")
REFERENCE = os.path.join(CONFIG, "reference.py")


def _tiny():
    with open(os.path.join(HERE, "testdata", "tiny-gigachat35",
                           "config.json")) as f:
        return json.load(f)


def test_reference_matches_the_programs_forward():
    from dynamo_tpu.models import llama
    from dynamo_tpu.models.config import ModelConfig

    forward = reference.load_forward(REFERENCE)
    hf32 = dict(_tiny(), torch_dtype="float32")
    cfg = ModelConfig.from_hf_config(hf32)
    assert (cfg.num_experts, cfg.experts_held, cfg.expert_first,
            cfg.num_experts_per_tok) == (16, 4, 4, 4)
    assert cfg.layer_ops == ("linear", "linear", "linear", "attn", "linear")
    # the seeded draw leaves no norm, tap, decay rate or bias trivial
    params = llama.init_params(cfg, jax.random.key(0))
    assert float(jnp.abs(params["linear_ops"]["attn_norm"]).max()) > 0
    toks = np.random.default_rng(0).integers(16, 512, 150)
    with jax.default_matmul_precision("highest"):
        want = llama.dense_forward(params, cfg, jnp.asarray(toks))
    got = forward(params, hf32, toks)
    # float32 both: the chunked form of the delta rule (blocks of 64, a
    # triangular solve a block) against the reference's token-by-token
    # scan, and the order of the sums; 4e-6 seen
    np.testing.assert_allclose(
        jax.nn.log_softmax(got), jax.nn.log_softmax(want), atol=2e-4)


def test_served_config_keeps_the_catalogs_values_but_the_reduced_keys():
    """config.json against what serve.json says was reduced: the tiny
    stand-in keeps the same keys, so the rehearsal parses what the chip
    serves."""
    with open(os.path.join(CONFIG, "config.json")) as f:
        served = json.load(f)
    with open(os.path.join(CONFIG, "serve.json")) as f:
        serve = json.load(f)
    assert serve["reduced"] == [
        "num_hidden_layers", "first_k_dense_replace", "n_routed_experts",
        "vocab_size", "num_nextn_predict_layers"]
    assert sorted(serve["reduced_why"]) == sorted(serve["reduced"])
    assert (served["num_hidden_layers"], served["first_k_dense_replace"],
            served["n_routed_experts"], served["vocab_size"],
            served["num_nextn_predict_layers"]) == (5, 1, 16, 16032, 0)
    assert served["expert_share"] == {"published": 256, "first": 0}
    assert served["full_attention_layers"] == list(range(3, 40, 4))
    assert set(_tiny()) == set(served)
    # every width as published
    for key, value in (
            ("hidden_size", 7168), ("intermediate_size", 18432),
            ("moe_intermediate_size", 2048), ("kv_lora_rank", 512),
            ("q_lora_rank", 1536), ("qk_nope_head_dim", 128),
            ("qk_rope_head_dim", 64), ("v_head_dim", 128),
            ("num_attention_heads", 64), ("num_experts_per_tok", 8),
            ("linear_num_key_heads", 32), ("linear_num_value_heads", 64),
            ("linear_key_head_dim", 128), ("linear_value_head_dim", 128),
            ("linear_conv_kernel_dim", 4), ("n_shared_experts", 1)):
        assert served[key] == value, key
    flags = serve["flags"]
    assert flags[flags.index("--state-snapshots") + 1] == "64"
    assert serve["rehearse"]["config"] == "tiny-gigachat35"
