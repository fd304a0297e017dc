"""A configuration's plain reference against the program's forward at
tiny size (OLMo-2: norm-after layers, full-width q/k norms, untied head).
(The rehearsal, ``run.py --rehearse``, compares it with the SERVED
engine: prefill, then decode through the paged cache.)"""

import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from chipbench import reference

HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.mark.parametrize("config, tiny", [("olmo2-1b", "tiny-olmo2")])
def test_reference_matches_the_programs_forward(config, tiny):
    from dynamo_tpu.models import llama
    from dynamo_tpu.models.config import ModelConfig

    forward = reference.load_forward(
        os.path.join(HERE, "configs", config, "reference.py"))
    with open(os.path.join(HERE, "testdata", tiny, "config.json")) as f:
        hf = json.load(f)
    hf32 = dict(hf, torch_dtype="float32")
    cfg = ModelConfig.from_hf_config(hf32)
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
