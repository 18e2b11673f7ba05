"""The Mistral-7B family: the dense block of `stacked_block.py`, nothing more.

What a family's file exports, and who calls it (README.md, "Adding a
configuration"): `model_config`, `make_params`, `reference_dims`,
`system_chain` with `ref_layer_params`, `hot_path`, `engine_state`,
`expected_kernels`, `state_leaves`."""

from __future__ import annotations

from . import stacked_block as block
from .stacked_block import (  # noqa: F401
    HotPath as hot_path,
    engine_state,
    expected_kernels,
    make_params,
    ref_layer_params,
    reference_dims,
    state_leaves,
    system_chain,
)


def model_config(spec: dict, name: str):
    return block.model_config(spec, name, block.FIELDS)
