"""The Mixtral family: Mistral's block with the feed-forward a sparse mixture
of experts. The program's config says so (`is_moe`), which makes the tree,
the chain and the hot path of `stacked_block.py` the expert ones; what
differs here is the two published keys and what the reference needs of them."""

from __future__ import annotations

from . import stacked_block as block
from .stacked_block import (  # noqa: F401
    HotPath as hot_path,
    engine_state,
    expected_kernels,
    make_params,
    ref_layer_params,
    state_leaves,
    system_chain,
)

FIELDS = {
    **block.FIELDS,
    "num_local_experts": "n_experts",
    "num_experts_per_tok": "n_experts_per_tok",
}


def model_config(spec: dict, name: str):
    return block.model_config(spec, name, FIELDS)


def reference_dims(spec: dict) -> dict:
    return {
        **block.reference_dims(spec),
        "n_experts": spec["num_local_experts"],
        "top_k": spec["num_experts_per_tok"],
    }
