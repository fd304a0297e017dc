"""``olmoe-1b-7b``'s plain reference against the program's forward at
tiny size (``testdata/tiny-olmoe``: pre-norm layers, full-width q/k
norms, 64 experts of which a token takes 8 without renormalisation).
(The rehearsal, ``run.py --rehearse``, compares it with the SERVED
engine; tier-1's ``tests/test_olmoe.py`` with the step programs.)"""

import json
import os

import jax
import jax.numpy as jnp
import numpy as np

from chipbench import reference

HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def test_reference_matches_the_programs_forward():
    from dynamo_tpu.models import llama
    from dynamo_tpu.models.config import ModelConfig

    forward = reference.load_forward(
        os.path.join(HERE, "configs", "olmoe-1b-7b", "reference.py"))
    with open(os.path.join(HERE, "testdata", "tiny-olmoe", "config.json")) as f:
        hf = json.load(f)
    hf32 = dict(hf, torch_dtype="float32")
    cfg = ModelConfig.from_hf_config(hf32)
    assert (cfg.num_experts, cfg.num_experts_per_tok) == (64, 8)
    params = llama.init_params(cfg, jax.random.key(0))
    # norms that are not all-ones, so a misplaced norm shows
    k = jax.random.key(1)
    params = jax.tree.map(
        lambda a: a if a.ndim > 2 or a.shape[-1] == cfg.vocab_size
        else a + 0.1 * jax.random.normal(k, a.shape, a.dtype), params)
    toks = np.random.default_rng(0).integers(16, 512, 40)
    with jax.default_matmul_precision("highest"):
        want = llama.dense_forward(params, cfg, jnp.asarray(toks))
    got = forward(params, hf32, toks)
    np.testing.assert_allclose(
        jax.nn.log_softmax(got), jax.nn.log_softmax(want), atol=2e-4)


def test_reference_refuses_what_is_not_olmoe():
    forward = reference.load_forward(
        os.path.join(HERE, "configs", "olmoe-1b-7b", "reference.py"))
    with open(os.path.join(HERE, "configs", "olmoe-1b-7b", "config.json")) as f:
        hf = json.load(f)
    for key, value in (("clip_qkv", 8.0), ("norm_topk_prob", True),
                       ("tie_word_embeddings", True)):
        try:
            forward({}, dict(hf, **{key: value}), [1, 2])
        except ValueError:
            continue
        raise AssertionError(f"{key}={value} was not refused")
