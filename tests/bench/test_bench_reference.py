"""The benchmark's plain references against the program, at a tiny size on
the CPU: Fig. A4 against ``LogisticRegressionAlgorithm.fit`` and the float32
Qwen2 forward against ``TransformerLM.forward``."""
import dataclasses
import pathlib
import sys

import jax
import jax.numpy as jnp
import numpy as np

sys.path.insert(0, str(pathlib.Path(__file__).resolve().parents[2]))

from bench.drivers import fit, serve  # noqa: E402
from bench.reference import qwen2  # noqa: E402
from bench.reference.fig_a4 import fig_a4  # noqa: E402

QWEN_TINY = {"name": "qwen2-tiny", "num_hidden_layers": 2, "hidden_size": 64,
             "num_attention_heads": 4, "num_key_value_heads": 2,
             "intermediate_size": 96, "vocab_size": 300, "rms_norm_eps": 1e-6,
             "rope_theta": 1000000.0, "tie_word_embeddings": True,
             "max_position_embeddings": 4096, "torch_dtype": "float32"}


def test_fig_a4_matches_the_estimator_fit():
    from repro.core.algorithms.logistic_regression import (
        LogisticRegressionAlgorithm)
    from repro.core.numeric_table import MLNumericTable

    rows, d, parts, batch, rounds, lr = 256, 40, 4, 8, 3, 64.0 / 40
    data = fit.make_table(2**31 + 7, rows, d, None)
    table = MLNumericTable(data, num_shards=parts)
    w = LogisticRegressionAlgorithm(
        learning_rate=lr, max_iter=rounds, local_batch_size=batch).fit(
            table).weights
    blocks = [(data, p * rows // parts, rows // parts) for p in range(parts)]
    w_ref = fig_a4(blocks, lr, rounds, batch)
    assert fit.rel_err(w, w_ref) < 1e-5
    # the labels split the rows about evenly, as the median does
    assert 0.3 < float(jnp.mean(data[:, 0])) < 0.7
    # the bfloat16 control is far from it
    assert fit.rel_err(fig_a4(blocks, lr, rounds, batch, "bfloat16"),
                       w_ref) > 1e-3


def test_qwen2_reference_matches_the_program_forward():
    from repro.models.transformer import TransformerLM

    arch = serve.arch_config(QWEN_TINY)
    params = serve.make_params(3, arch, {"std": {"default": 0.02}})
    # give the norms weights other than 1 and the biases their own values,
    # so that the layout mapping is exercised
    params = jax.tree_util.tree_map_with_path(
        lambda path, x: x + 0.1 * jax.random.normal(
            jax.random.PRNGKey(len(str(path))), x.shape, x.dtype)
        if serve._leaf_name(path) in ("scale", "bq", "bk", "bv") else x,
        params)
    tokens = np.random.default_rng(0).integers(0, 300, size=37)
    with jax.default_matmul_precision("highest"):
        logits, _ = TransformerLM(arch).forward(params,
                                                jnp.asarray(tokens)[None])
    embed = params["embed"]["tok"]
    final = 1.0 + params["final_norm"]["scale"]
    h = qwen2.hidden(QWEN_TINY, lambda i: serve.layer_view(params, i),
                     embed, final, tokens, 64)[:37]
    ours = np.asarray(h @ embed.T)
    theirs = np.asarray(logits[0])
    assert np.max(np.abs(ours - theirs)) / np.max(np.abs(theirs)) < 1e-4
    best, std, pick = qwen2.logit_rows(h, embed,
                                       jnp.asarray(np.argmax(theirs, -1)))
    assert np.allclose(best, pick, atol=1e-4 * float(np.max(best)))
    assert np.all(std > 0)


def test_arch_config_keeps_every_published_width():
    arch = serve.arch_config(QWEN_TINY)
    assert (arch.num_layers, arch.d_model, arch.num_heads, arch.num_kv_heads,
            arch.d_ff, arch.vocab_size) == (2, 64, 4, 2, 96, 300)
    assert arch.rope_base == 1e6 and arch.qkv_bias and arch.tie_embeddings
    assert dataclasses.replace(arch).dtype == "float32"
