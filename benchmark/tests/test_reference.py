"""lib/reference.py against the program's own model at a tiny size, float32:
the two are written apart and have to agree to rounding."""
import jax
import jax.numpy as jnp
import numpy as np

from benchmark.lib import reference, weights
from benchmark.runners import _llama

CFG = dict(_llama.TINY, max_position_embeddings=64, rms_norm_eps=1e-5,
           rope_theta=1e6, tie_word_embeddings=False)


def _forward(ids, precision="f32"):
    w = weights.make_all(CFG, jnp.float32)(np.uint32(3))
    h = jnp.take(w["embed"], ids, axis=0)
    for i in range(CFG["num_hidden_layers"]):
        lp = {n: w[f"layers.{i}.{n}"] for n in weights.LAYER_LEAVES}
        h = reference.layer_forward(lp, h, CFG, precision)
    return reference.head_logits(w["final_norm"], w["head"], h, CFG, precision)


def test_forward_matches_llama_for_causal_lm():
    from paddle_tpu.core.tensor import Tensor
    model = _llama.build_model(CFG, np.uint32(3), "float32")
    ids = np.random.default_rng(0).integers(0, CFG["vocab_size"], (2, 24), dtype=np.int32)
    with jax.default_matmul_precision("highest"):
        got = np.asarray(model(Tensor(jnp.asarray(ids)))._data)
    ref = np.asarray(_forward(jnp.asarray(ids)))
    assert np.abs(got - ref).max() <= 2e-4 * np.abs(ref).max()


def test_weights_are_the_same_whoever_makes_them():
    a = weights.make_all(CFG, jnp.bfloat16)(np.uint32(9))
    layer = weights.make_layer(CFG, jnp.bfloat16)(np.uint32(9), jnp.int32(1))
    ends = weights.make_ends(CFG, jnp.bfloat16)(np.uint32(9))
    assert all(bool((a[f"layers.1.{n}"] == layer[n]).all()) for n in weights.LAYER_LEAVES)
    assert bool((a["embed"] == ends[0]).all()) and bool((a["head"] == ends[2]).all())
    assert sorted(n for n, _ in weights.leaf_specs(CFG)) == sorted(a)
    assert abs(float(a["layers.0.gate"].astype(jnp.float32).std()) - weights.INIT_STD) < 2e-3


def test_lower_precision_moves_the_logits():
    ids = jnp.asarray(np.random.default_rng(1).integers(0, 128, (1, 16), dtype=np.int32))
    ref = _forward(ids)
    for precision, least in (("bf16", 1e-4), ("fp8", 1e-3)):
        low = _forward(ids, precision)
        assert float(jnp.abs(low - ref).max() / jnp.abs(ref).max()) > least
