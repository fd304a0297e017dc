"""``lfm2-8b-a1b``'s plain reference against the program's forward at
tiny size (``testdata/tiny-lfm2``: ``c c A c`` twice, two dense FFNs then
six expert layers of 8 experts, 2 a token, a sigmoid router with a
selection bias, a tied head). (The rehearsal, ``run.py --rehearse``,
compares it with the SERVED engine; tier-1's ``tests/test_lfm2.py`` with
the step programs and the engine's state handling.)"""

import json
import os

import jax
import jax.numpy as jnp
import numpy as np

from chipbench import reference

HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
REFERENCE = os.path.join(HERE, "configs", "lfm2-8b-a1b", "reference.py")


def test_reference_matches_the_programs_forward():
    from dynamo_tpu.models import llama
    from dynamo_tpu.models.config import ModelConfig

    forward = reference.load_forward(REFERENCE)
    with open(os.path.join(HERE, "testdata", "tiny-lfm2", "config.json")) as f:
        hf = json.load(f)
    hf32 = dict(hf, torch_dtype="float32")
    cfg = ModelConfig.from_hf_config(hf32)
    assert (cfg.num_experts, cfg.num_experts_per_tok) == (8, 2)
    assert cfg.layer_ops == ("conv", "conv", "attn", "conv") * 2
    params = llama.init_params(cfg, jax.random.key(0))
    # norms that are not all-ones, so a misplaced norm shows
    k = jax.random.key(1)
    params = {
        grp: {n: a + 0.1 * jax.random.normal(k, a.shape, a.dtype)
              if n.endswith("norm") else a for n, a in leaves.items()}
        if isinstance(leaves, dict) else leaves
        for grp, leaves in params.items()}
    toks = np.random.default_rng(0).integers(16, 512, 40)
    with jax.default_matmul_precision("highest"):
        want = llama.dense_forward(params, cfg, jnp.asarray(toks))
    taps = []
    got = forward(params, hf32, toks, taps=taps)
    np.testing.assert_allclose(
        jax.nn.log_softmax(got), jax.nn.log_softmax(want), atol=2e-4)
    assert len(taps) == 6  # the expert layers: not the two dense ones


def test_reference_refuses_what_is_not_lfm2_8b_a1b():
    forward = reference.load_forward(REFERENCE)
    with open(os.path.join(HERE, "configs", "lfm2-8b-a1b",
                           "config.json")) as f:
        hf = json.load(f)
    for key, value in (("model_type", "lfm2"), ("conv_bias", True),
                       ("norm_topk_prob", False), ("use_expert_bias", False),
                       ("tie_word_embeddings", False),
                       ("rope_scaling", {"factor": 2.0})):
        try:
            forward({}, dict(hf, **{key: value}), [1, 2])
        except ValueError:
            continue
        raise AssertionError(f"{key}={value} was not refused")
