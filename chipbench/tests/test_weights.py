"""The reference draws each layer alone and must get the values of the
stacked leaf the engine holds."""
import jax.numpy as jnp
import numpy as np

from chipbench import weights as W

M = dict(num_layers=3, d_model=32, num_heads=4, num_kv_heads=2, head_dim=8,
         d_ff=64, vocab=300, gated=True, parallel_block=False,
         tie_embeddings=False, embed_scale=0.02, dtype="bfloat16")


def test_layer_draws_match_the_stacked_leaves():
    seed = 2**40 + 123
    params = W.make_params(M, seed, jnp.bfloat16)
    root = W.root_of(*W.seed_key(seed))
    assert params["embed"].shape == (W.padded_vocab(300), 32)
    for layer in range(3):
        one = W.layer_weights(M, root, layer, jnp.bfloat16)
        assert set(one) == set(params["layers"])
        for name, w in one.items():
            np.testing.assert_array_equal(np.asarray(w),
                                          np.asarray(params["layers"][name][layer]))
    for name in ("embed", "final_norm", "lm_head"):
        np.testing.assert_array_equal(
            np.asarray(W.top_weight(M, root, name, jnp.bfloat16)),
            np.asarray(params[name]))


def test_seeds_differ_past_32_bits():
    a = W.make_params(M, 5, jnp.bfloat16)["layers"]["wq"]
    b = W.make_params(M, 5 + 2**32, jnp.bfloat16)["layers"]["wq"]
    assert not np.array_equal(np.asarray(a), np.asarray(b))
