"""BERT (Devlin et al. 2018), masked-LM objective: token, position and
segment embeddings with a layer norm, post-norm encoder blocks with
bidirectional attention and a GELU MLP, and the MLM head (dense, GELU, layer
norm, then the token embedding as the output matrix). Departures from the
paper are the configuration file's: no next-sentence head, no output bias,
segment 0 everywhere, no padding, no dropout."""
from __future__ import annotations

import jax

from benchmarks.reference import common


def nll(params, tokens, labels, config: dict):
    """tokens, labels [s] -> (summed NLL over labelled positions, count)."""
    eps = config["layer_norm_eps"]
    x = params["wte"][tokens] + params["wpe"][:tokens.shape[0]] \
        + params["wtype"][0]
    x = common.layer_norm(x, params["emb_ln_g"], params["emb_ln_b"], eps)

    def block(x, p):
        a = common.attention(x, p["qkv_w"], p["qkv_b"], p["proj_w"],
                             p["proj_b"], config["num_attention_heads"],
                             causal=False)
        x = common.layer_norm(x + a, p["ln1_g"], p["ln1_b"], eps)
        y = common.gelu_tanh(x @ p["fc_w"] + p["fc_b"])
        y = y @ p["fo_w"] + p["fo_b"]
        return common.layer_norm(x + y, p["ln2_g"], p["ln2_b"], eps), None

    # the loop over the layers, whose parameters are stacked on axis 0
    x, _ = jax.lax.scan(block, x, params["blocks"])
    x = common.gelu_tanh(x @ params["mlm_w"] + params["mlm_b"])
    x = common.layer_norm(x, params["mlm_ln_g"], params["mlm_ln_b"], eps)
    return common.nll_sum(x @ params["wte"].T, labels)


def decayed(params):
    """Weight decay on the matrices and the embeddings, none on biases and
    layer-norm parameters (Devlin et al.'s optimizer; models/bert.py
    agrees)."""
    return jax.tree_util.tree_map_with_path(
        lambda path, _: path[-1].key in ("wte", "wpe", "wtype")
        or path[-1].key.endswith("_w"), params)
