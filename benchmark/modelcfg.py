"""Configuration files: `benchmark/configs/<name>.json` → the program's
`ModelConfig`, registered under the configuration's name. Which published
keys a configuration has, and what each means to the program, is its
family's to say: `families/<family>.py`, found by the file's `family` key."""

from __future__ import annotations

import functools
import importlib
import importlib.util
import json
from pathlib import Path
from typing import Iterable

HERE = Path(__file__).resolve().parent

# what a configuration file holds besides published keys
_HARNESS_KEYS = {
    "source", "family", "assumed", "reduced", "deployment", "serving", "weights", "check",
}


def load_json(kind: str, name: str, root: Path = HERE) -> dict:
    path = root / kind / f"{name}.json"
    if not path.is_file():
        raise FileNotFoundError(f"no {kind[:-1]} named {name!r}: {path} is missing")
    return json.loads(path.read_text())


@functools.lru_cache(maxsize=None)
def load_module(kind: str, name: str, root: Path = HERE):
    """`<root>/<kind>/<name>.py` where a cell's files bring their own (the
    harness's tests do), else the harness's `<kind>/<name>.py`."""
    path = root / kind / f"{name}.py"
    if root == HERE or not path.is_file():
        return importlib.import_module(f"{kind}.{name}")
    found = importlib.util.spec_from_file_location(f"{root.name}_{kind}_{name}", path)
    module = importlib.util.module_from_spec(found)
    found.loader.exec_module(module)
    return module


def refuse_unmapped(spec: dict, mapped: Iterable[str], name: str) -> None:
    """A family calls this with the published keys it maps: any other key of
    the file that is not the harness's own raises, by name."""
    unknown = sorted(set(spec) - set(mapped) - _HARNESS_KEYS)
    if unknown:
        raise ValueError(f"{name}: keys that map onto no ModelConfig field: {unknown}")


def model_config(spec: dict, name: str, root: Path = HERE):
    return load_module("families", spec["family"], root).model_config(spec, name)


def register_preset(spec: dict, name: str, root: Path = HERE):
    """`tpu-serving` takes `model:` only from MODEL_PRESETS, so the
    configuration is entered there under its own name: the one place the
    harness writes into the program's tables (PERF.md lists "the resource
    reads a model configuration file" for a later PR)."""
    from langstream_tpu.models.configs import MODEL_PRESETS

    config = model_config(spec, name, root)
    MODEL_PRESETS[name] = config
    return config
