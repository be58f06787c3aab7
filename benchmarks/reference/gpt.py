"""GPT-2 / GPT-3 (Radford et al. 2019; Brown et al. 2020): learned token and
position embeddings, pre-norm decoder blocks with causal attention and a
GELU MLP, a final layer norm and the token embedding as the output matrix.
Departures from the papers are the configuration file's (no dropout; GPT-3's
banded sparse layers are dense here)."""
from __future__ import annotations

import jax

from benchmarks.reference import common


def nll(params, tokens, labels, config: dict):
    """tokens, labels [s] -> (summed NLL over labelled positions, count)."""
    eps = config["layer_norm_epsilon"]
    x = params["wte"][tokens] + params["wpe"][:tokens.shape[0]]

    def block(x, p):
        y = common.layer_norm(x, p["ln1_g"], p["ln1_b"], eps)
        x = x + common.attention(y, p["qkv_w"], p["qkv_b"], p["proj_w"],
                                 p["proj_b"], config["n_head"], causal=True)
        y = common.layer_norm(x, p["ln2_g"], p["ln2_b"], eps)
        y = common.gelu_tanh(y @ p["fc_w"] + p["fc_b"])
        return x + y @ p["fo_w"] + p["fo_b"], None

    # the loop over the layers, whose parameters are stacked on axis 0
    x, _ = jax.lax.scan(block, x, params["blocks"])
    x = common.layer_norm(x, params["lnf_g"], params["lnf_b"], eps)
    return common.nll_sum(x @ params["wte"].T, labels)


def decayed(params):
    """Weight decay on the matrices and the embeddings, none on biases and
    layer-norm parameters (the usual GPT practice; models/gpt.py agrees)."""
    return jax.tree_util.tree_map_with_path(
        lambda path, _: path[-1].key in ("wte", "wpe")
        or path[-1].key.endswith("_w"), params)
