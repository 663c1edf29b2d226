"""Build the program's model from a configuration file, and check that the
program's registry still runs the sizes the file states."""
from __future__ import annotations

import importlib
from typing import Any, Dict, Tuple

# configuration-file key -> ModelConfig field
FIELDS = {"num_hidden_layers": "num_layers", "hidden_size": "d_model",
          "intermediate_size": "d_ff", "num_attention_heads": "num_heads",
          "num_key_value_heads": "num_kv_heads", "vocab_size": "vocab_size",
          "norm_epsilon": "norm_eps", "rope_theta": "rope_theta"}


def sizes(config: Dict[str, Any]) -> Dict[str, Any]:
    """The numbers the FLOP counts and the reference read."""
    s = {k: config[k] for k in FIELDS}
    s["head_dim"] = config["assumed"]["head_dim"]
    return s


def build(config: Dict[str, Any]):
    """``(model, sizes)``: the program's model for ``program.arch`` (with
    ``program.overrides``, the cuts the file lists under ``reduced``
    applied); raises if it differs from the file's sizes."""
    from repro.config import get_config
    from repro.models.builder import build_model
    prog = config["program"]
    cfg = get_config(prog["arch"], reduced=bool(prog.get("reduced", False)))
    if prog.get("overrides"):
        cfg = cfg.replace(**prog["overrides"])
    s = sizes(config)
    want = dict({FIELDS[k]: v for k, v in s.items() if k in FIELDS},
                head_dim=s["head_dim"],
                tie_embeddings=config["tie_word_embeddings"],
                sliding_window=config["sliding_window"] or 0)
    got = {k: getattr(cfg, k) for k in want}
    if got != want:
        raise ValueError(f"{config['name']}: the program runs {got}, the "
                         f"configuration file states {want}")
    return build_model(cfg), s


def reference(config: Dict[str, Any]):
    """The plain reference module named beside the configuration."""
    return importlib.import_module(
        f"chipbench.configs.{config['reference']}")


def param_tree(model) -> Tuple[Any, Any]:
    """``(boxed, raw)``: the program's Boxed parameter tree as shapes, and
    the raw tree as the train step holds it, both from ``jax.eval_shape``
    of the program's own init."""
    import jax
    from repro.models import layers as L
    key = jax.ShapeDtypeStruct((), jax.random.key(0).dtype)
    boxed = model.abstract_params()
    raw = jax.eval_shape(lambda k: L.unbox(model.init(k)), key)
    return boxed, raw
