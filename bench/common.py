"""Helpers the generators share: the program's model built from a configuration
file, and leaves named by their dotted paths."""

from __future__ import annotations

import dataclasses


def model_of(cfg: dict):
    """The program's model for the configuration's ``model`` block (keys the
    program's ``ModelConfig`` does not take, such as ``norm_eps``, are the
    reference's)."""
    from repro.models import build_model
    from repro.models.config import ModelConfig

    known = {f.name for f in dataclasses.fields(ModelConfig)}
    return build_model(ModelConfig(**{k: v for k, v in cfg["model"].items() if k in known}))


def nest(flat: dict) -> dict:
    """``{"a.b": x}`` -> ``{"a": {"b": x}}``."""
    out: dict = {}
    for name, leaf in flat.items():
        *path, last = name.split(".")
        d = out
        for k in path:
            d = d.setdefault(k, {})
        d[last] = leaf
    return out


def leaf_names(tree) -> dict:
    """``{"a": {"b": x}}`` -> ``{"a.b": x}``."""
    import jax

    return {".".join(str(getattr(k, "key", k)) for k in path): leaf
            for path, leaf in jax.tree_util.tree_flatten_with_path(tree)[0]}
