"""LLaMA + BERT/ERNIE model families: loss decreases under the compiled
trainer, GQA/ RoPE correctness properties, sharded meshes compile
(the semi_auto_llama-style coverage, SURVEY §4)."""
import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax.sharding import Mesh


def _mesh(shape, names):
    n = int(np.prod(shape))
    devs = np.asarray(jax.devices()[:n]).reshape(shape)
    return Mesh(devs, names)


def test_llama_train_step_loss_decreases():
    from paddle_tpu.models.llama import LLAMA_CONFIGS, build_train_step
    import dataclasses
    config = dataclasses.replace(LLAMA_CONFIGS["llama-tiny"],
                                 dtype="float32")
    init_fn, step = build_train_step(config, lr=1e-3, remat=False)
    state = init_fn(0)
    rng = np.random.RandomState(0)
    tokens = jnp.asarray(rng.randint(0, 1024, (4, 64)), jnp.int32)
    labels = jnp.asarray(rng.randint(0, 1024, (4, 64)), jnp.int32)
    losses = []
    for _ in range(10):
        state, loss = step(state, tokens, labels)
        losses.append(float(loss))
    assert losses[-1] < losses[0]
    assert np.isfinite(losses).all()


def test_llama_gqa_heads_repeat():
    """kv heads < q heads must still produce finite logits of right
    shape."""
    from paddle_tpu.models.llama import (LlamaConfig, init_llama_params,
                                         llama_forward)
    c = LlamaConfig(vocab_size=128, hidden_size=64, intermediate_size=128,
                    num_layers=2, num_heads=8, num_kv_heads=2,
                    max_position_embeddings=32, dtype="float32")
    params = init_llama_params(c, 0)
    tokens = jnp.zeros((2, 16), jnp.int32)
    logits = llama_forward(params, tokens, c, remat=False)
    assert logits.shape == (2, 16, 128)
    assert bool(jnp.all(jnp.isfinite(logits)))


def test_llama_rope_position_dependence():
    """RoPE: shifting a token's position must change its logits (unlike a
    no-PE model)."""
    from paddle_tpu.models.blocks import rope
    x = jnp.ones((1, 4, 2, 8), jnp.float32)
    r = rope(x, 10000.0)
    # same content at different positions must differ after rotation
    assert not np.allclose(np.asarray(r[0, 0]), np.asarray(r[0, 3]))
    # norm is preserved (rotation)
    np.testing.assert_allclose(np.linalg.norm(np.asarray(r[0, 0])),
                               np.linalg.norm(np.asarray(x[0, 0])),
                               rtol=1e-5)


def test_llama_sharded_dp_mp_pp():
    from paddle_tpu.models.llama import LLAMA_CONFIGS, build_train_step
    import dataclasses
    config = dataclasses.replace(LLAMA_CONFIGS["llama-tiny"],
                                 dtype="float32")
    mesh = _mesh((2, 2, 2), ("dp", "pp", "mp"))
    init_fn, step = build_train_step(config, mesh=mesh, lr=1e-3,
                                     remat=True, pp_microbatches=2)
    state = init_fn(0)
    rng = np.random.RandomState(1)
    tokens = jnp.asarray(rng.randint(0, 1024, (4, 32)), jnp.int32)
    state, loss = step(state, tokens, tokens)
    assert np.isfinite(float(loss))


def test_bert_mlm_train_step_and_masking():
    from paddle_tpu.models.bert import BERT_CONFIGS, build_train_step
    import dataclasses
    config = dataclasses.replace(BERT_CONFIGS["bert-tiny"],
                                 dtype="float32")
    init_fn, step = build_train_step(config, lr=1e-3, remat=False)
    state = init_fn(0)
    rng = np.random.RandomState(0)
    tokens = jnp.asarray(rng.randint(0, 1024, (4, 32)), jnp.int32)
    labels = jnp.where(jnp.asarray(rng.rand(4, 32)) < 0.15, tokens, -100)
    losses = []
    for _ in range(10):
        state, loss = step(state, tokens, labels)
        losses.append(float(loss))
    assert losses[-1] < losses[0]


def test_bert_attention_mask_zeroes_padding_influence():
    from paddle_tpu.models.bert import (BertConfig, bert_encode,
                                        init_bert_params)
    c = BertConfig(vocab_size=128, hidden_size=32, num_layers=2,
                   num_heads=2, intermediate_size=64,
                   max_position_embeddings=32, dtype="float32")
    params = init_bert_params(c, 0)
    rng = np.random.RandomState(2)
    base = rng.randint(1, 128, (1, 16))
    t1 = jnp.asarray(base, jnp.int32)
    t2 = jnp.asarray(np.concatenate(
        [base[:, :8], rng.randint(1, 128, (1, 8))], 1), jnp.int32)
    mask = jnp.asarray(np.concatenate(
        [np.ones((1, 8)), np.zeros((1, 8))], 1), jnp.float32)
    e1 = bert_encode(params, t1, attention_mask=mask, config=c,
                     remat=False)
    e2 = bert_encode(params, t2, attention_mask=mask, config=c,
                     remat=False)
    # masked tail differs, but visible-position encodings must match
    np.testing.assert_allclose(np.asarray(e1[:, :8]),
                               np.asarray(e2[:, :8]), rtol=1e-4,
                               atol=1e-4)


def test_ernie_config_registered():
    from paddle_tpu.models.bert import BERT_CONFIGS
    c = BERT_CONFIGS["ernie-3.0-base"]
    assert c.hidden_size == 768 and c.num_layers == 12


def test_llama_untied_head_differs_from_embedding():
    from paddle_tpu.models.llama import LlamaConfig, init_llama_params
    c = LlamaConfig(vocab_size=64, hidden_size=32, intermediate_size=64,
                    num_layers=1, num_heads=2, tie_embeddings=False,
                    dtype="float32")
    p = init_llama_params(c, 0)
    assert not np.allclose(np.asarray(p["lm_head"]), np.asarray(p["wte"]))
