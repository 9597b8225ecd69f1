"""The seam between the decode engine and a model (`serving.py`'s module
docstring), from Llama's side.

- The served Llama layer (`models/llama.LlamaServe`) against the training
  model below the level of a token stream: a whole prompt as ONE chunk
  through `eng._forward_paged` gives, at every position, the logits of
  `model(ids)`. Greedy streams alone held the two implementations of the
  Llama block together before.
- The engine's one way in is `model.serve_model()`, and `serving.py` holds
  no model: it imports nothing under `paddle_tpu.models`.
"""
import ast

import jax.numpy as jnp
import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu.models.llama import (LlamaConfig, LlamaForCausalLM,
                                     LlamaServe)
from paddle_tpu.serving import PagedLlamaDecodeEngine


@pytest.mark.parametrize("kv_heads,tied", [(4, False), (2, False),
                                           (4, True), (2, True)],
                         ids=["mha-untied", "gqa2-untied", "mha-tied",
                              "gqa2-tied"])
def test_served_llama_logits_match_the_training_model(kv_heads, tied):
    """float32, 29 tokens in one 32-row chunk over blocks of 8: every
    position's logits within float32 rounding of the model's own full
    forward (another attention, another matmul orientation, no cache)."""
    paddle.seed(11)
    cfg = LlamaConfig.tiny(vocab_size=96, hidden_size=32,
                           intermediate_size=64, num_hidden_layers=2,
                           num_attention_heads=4,
                           num_key_value_heads=kv_heads,
                           tie_word_embeddings=tied,
                           use_flash_attention=False)
    model = LlamaForCausalLM(cfg)
    eng = PagedLlamaDecodeEngine(model, max_slots=2, max_seq=64,
                                 block_size=8, prefill_chunk=32)
    assert isinstance(eng._m, LlamaServe)
    assert (eng.params["head"] is eng.params["emb"]) == tied
    n, bucket, slot = 29, 32, 1
    ids = np.random.default_rng(n).integers(0, 96, n).astype(np.int32)
    assert eng.begin_request(slot, ids, 4)
    padded = np.zeros((1, bucket), np.int32)
    padded[0, :n] = ids
    offs = jnp.arange(bucket)
    logits, _, aux, _ = eng._forward_paged(
        eng.params, eng.kvs, jnp.asarray(padded), offs[None, :],
        eng._tables_dev(slot)[None, :], (n - 1) // eng.block_size + 1,
        (offs < n)[None, :])
    assert aux is None
    want = np.asarray(model(paddle.to_tensor(ids[None, :]))._data)[0]
    got = np.asarray(logits)[0, :n]
    assert got.shape == want.shape == (n, 96)
    # reads 2e-7 to 2e-6 where the logits reach 0.4 to 2.4
    assert np.abs(got - want).max() < 1e-5
    # and the stream that follows is the model's own
    eng.release(slot)
    ref = np.asarray(model.generate(paddle.to_tensor(ids[None, :]),
                                    max_new_tokens=6)._data)[0, n:]
    assert eng.generate(ids, max_new_tokens=6, slot=slot) == ref.tolist()


def test_a_model_without_serve_model_is_refused():
    class Bare:
        config = LlamaConfig.tiny()

    with pytest.raises(TypeError, match=r"Bare has no serve_model\(\)"):
        PagedLlamaDecodeEngine(Bare(), max_slots=2, max_seq=64)


def test_serving_imports_no_model():
    """The arrow points one way: models import nothing of the engine at
    import time and the engine imports no model at all."""
    import paddle_tpu.serving as serving
    tree = ast.parse(open(serving.__file__).read())
    imported = []
    for node in ast.walk(tree):
        if isinstance(node, ast.Import):
            imported += [a.name for a in node.names]
        elif isinstance(node, ast.ImportFrom):
            mod = "." * node.level + (node.module or "")
            imported += [f"{mod}.{a.name}" if node.level else mod
                         for a in node.names]
    assert imported, "serving.py imports something"
    assert [m for m in imported if "models" in m.split(".")] == []
    defined = {n.name for n in ast.walk(tree)
               if isinstance(n, (ast.FunctionDef, ast.ClassDef))}
    assert not defined & {"_LlamaServe", "_block_paged", "_build_llama_params",
                          "_mm", "_rms", "_rope", "_quantize_w"}
