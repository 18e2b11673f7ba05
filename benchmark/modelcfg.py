"""Configuration files: `benchmark/configs/<name>.json` → the program's
`ModelConfig`, registered under the configuration's name."""

from __future__ import annotations

import json
from pathlib import Path

HERE = Path(__file__).resolve().parent

# published config.json key → ModelConfig field
_FIELDS = {
    "vocab_size": "vocab_size",
    "hidden_size": "d_model",
    "num_hidden_layers": "n_layers",
    "num_attention_heads": "n_heads",
    "num_key_value_heads": "n_kv_heads",
    "intermediate_size": "d_ff",
    "head_dim": "head_dim",
    "rope_theta": "rope_theta",
    "rms_norm_eps": "rms_norm_eps",
    "max_position_embeddings": "max_seq_len",
    "hidden_act": "activation",
    "tie_word_embeddings": "tie_embeddings",
    "num_local_experts": "n_experts",
    "num_experts_per_tok": "n_experts_per_tok",
}


# what a configuration file holds besides published keys
_HARNESS_KEYS = {
    "source", "family", "assumed", "reduced", "deployment", "serving", "weights",
    "check", "sliding_window",
}


def load_json(kind: str, name: str, root: Path = HERE) -> dict:
    path = root / kind / f"{name}.json"
    if not path.is_file():
        raise FileNotFoundError(f"no {kind[:-1]} named {name!r}: {path} is missing")
    return json.loads(path.read_text())


def model_config(spec: dict, name: str):
    from langstream_tpu.models.configs import ModelConfig

    unknown = sorted(set(spec) - set(_FIELDS) - _HARNESS_KEYS)
    if unknown:
        raise ValueError(f"{name}: keys that map onto no ModelConfig field: {unknown}")
    if spec.get("sliding_window") is not None:
        raise ValueError(f"{name}: the program's block has no sliding window")
    fields = {ours: spec[theirs] for theirs, ours in _FIELDS.items() if theirs in spec}
    return ModelConfig(name=name, **fields)


def register_preset(spec: dict, name: str):
    """`tpu-serving` takes `model:` only from MODEL_PRESETS, so the
    configuration is entered there under its own name: the one place the
    harness writes into the program's tables (PERF.md lists "the resource
    reads a model configuration file" for a later PR)."""
    from langstream_tpu.models.configs import MODEL_PRESETS

    config = model_config(spec, name)
    MODEL_PRESETS[name] = config
    return config
